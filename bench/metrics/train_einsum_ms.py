"""Log-einsum-exp segments: mean device time per step under the
``plan.fused``, ``plan.gather`` and ``plan.layer`` scopes (``core/einet.py``,
``core/layers.py``), forward and backward, in ms.
None where the trace has nothing under that name."""

from harness import scopes


def read(run):
    return scopes.layer_ms(run.get("trace")).get("train_einsum_ms")
