"""EM step, wait on device: mean host time per step in the program's
``train.sync`` span (the wait for the step's mean LL), over the
``bench.step`` spans inside the traced window, in ms.
None where the trace has nothing under that name."""

from harness import scopes


def read(run):
    return scopes.layer_ms(run.get("trace")).get("train_sync_ms")
