"""M-step and Sato blend: mean device time per step under the ``em.mstep``
scope (``core/em.py``), in ms.
None where the trace has nothing under that name."""

from harness import scopes


def read(run):
    return scopes.layer_ms(run.get("trace")).get("train_mstep_ms")
