"""EM step, host side: mean host time per step in the program's
``train.dispatch`` span (the call of the compiled step), over the
``bench.step`` spans inside the traced window, in ms.
None where the trace has nothing under that name."""

from harness import scopes


def read(run):
    return scopes.layer_ms(run.get("trace")).get("train_dispatch_ms")
