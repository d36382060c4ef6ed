"""Leaf rows and statistics: mean device time per step under the
``einet.leaf`` and ``em.leaf_stats`` scopes (``core/einet.py``,
``core/em.py``), a loop op counted by its body alone, in ms.
None where the trace has nothing under that name."""

from harness import scopes


def read(run):
    return scopes.layer_ms(run.get("trace")).get("train_leaf_ms")
