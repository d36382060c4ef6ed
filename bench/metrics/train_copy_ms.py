"""Host to device: mean host time per step in the program's ``train.copy``
span (``train/pipeline.py`` ``run_step``: the batch's ``jnp.asarray``), over
the ``bench.step`` spans inside the traced window, in ms.
None where the trace has nothing under that name."""

from harness import scopes


def read(run):
    return scopes.layer_ms(run.get("trace")).get("train_copy_ms")
