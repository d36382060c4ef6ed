"""Faults and the lower-precision control, planted in the program.  Each
takes effect for programs traced after it is planted."""

import jax


def precision_high():
    """The control: every contraction of the model at ``HIGH`` (three bf16
    passes) instead of the configured ``HIGHEST``."""
    from repro.core import layers

    layers.PRECISION = jax.lax.Precision.HIGH


def state_unchanged():
    """The EM step returns the state it was given."""
    from repro.train import pipeline

    pipeline.blend_params = lambda model, params, mini, step_size: params


def half_batch():
    """The E-step sees only the first half of each (per-chip) batch."""
    from repro.train import pipeline

    orig = pipeline.em_statistics

    def half(model, params, x, axis_names=None):
        return orig(model, params, x[: x.shape[0] // 2], axis_names)

    pipeline.em_statistics = half


def no_exchange():
    """The statistics are not summed across chips."""
    from repro.core import em

    em._psum = lambda x, axis_names: x


PLANTS = {f.__name__: f for f in (precision_high, state_unchanged, half_batch,
                                  no_exchange)}
