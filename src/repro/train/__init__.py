"""Compiled EM training pipeline (the training-side twin of ``repro.serve``)."""

from repro.train.pipeline import (
    TrainConfig,
    em_update_microbatched,
    fit,
    make_em_step,
    make_sharded_em_step,
    microbatched_em_statistics,
    record_step,
    run_step,
    stochastic_em_update_microbatched,
)

__all__ = [
    "TrainConfig",
    "em_update_microbatched",
    "fit",
    "make_em_step",
    "make_sharded_em_step",
    "microbatched_em_statistics",
    "record_step",
    "run_step",
    "stochastic_em_update_microbatched",
]
