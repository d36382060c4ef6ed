"""Compiled EM training pipeline: the training-side twin of ``repro.serve``.

The paper's EM step is two phases -- an E-step that is one ``jax.grad`` call
(§3.5) and a closed-form M-step -- but the *seed* hot path still ran them as
separate dispatches, accumulated microbatch statistics in a Python loop, and
never donated the old parameter buffers.  This module makes the whole update
one compiled, donated-buffer XLA program:

  * ``microbatched_em_statistics`` folds ``accumulate_statistics`` over the
    microbatch axis with ``lax.scan`` (one compiled body, no per-microbatch
    dispatch, no host round-trips) -- full-batch EM on datasets larger than
    one device batch is a single program.
  * ``em_update_microbatched`` / ``stochastic_em_update_microbatched`` fuse
    scan-E-step + M-step (+ Sato blend) into one jittable function.
  * ``make_em_step`` returns the jitted update with the parameter pytree
    donated (the M-step writes a fresh pytree of identical shape, so the old
    buffers are dead the moment statistics are read -- donation halves peak
    parameter memory on TPU/GPU).

With ``EiNet(impl="pallas")`` the E-step grad flows through the fused
backward Pallas kernel (``repro.kernels``), making the entire update --
forward, backward, accumulate, M-step -- a single fused program: the
"compiled EM step" row of EXPERIMENTS.md §Perf, benchmarked by
``benchmarks/bench_train.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import compile as compile_lib
from repro import obs
from repro.core.einet import EiNet
from repro.core.em import (
    EMConfig,
    accumulate_statistics,
    blend_params,
    em_statistics,
    m_step,
    psum_statistics,
    zeros_like_statistics,
)
from repro.obs import health as health_lib

# At or below this many microbatches the accumulation loop is UNROLLED into
# the jitted program instead of lowered as ``lax.scan``.  This threshold is
# MEASURED, not assumed -- and the measurement says the scan wins at every
# (arch, microbatch) cell on the CPU container (unroll 1.03-2.02x the scan
# time at microbatches in {2,4,8} on the smoke arch and einet_rat: XLA
# optimizes one scan body better than N fused copies), so the threshold is
# 1: only the microbatches == 1 case skips the scan, via the direct
# ``em_statistics`` fast path below.  The einet_rat speedup-below-1.0
# BENCH_train.json regression this was suspected of causing was actually the
# seed's gather-based per-layer forward dominating the scan body at small
# arch; with depth-grouped (static-slice) execution the scan-accumulated
# step beats the per-dispatch path (x1.10 at einet_rat, batch 256, mb 4).
# Both lowerings add identical terms in identical order; totals agree to
# float32 roundoff.  ``TrainConfig.scan_microbatches`` overrides per step.
SCAN_UNROLL_MAX = 1


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Configuration for one compiled EM update step.

    mode: "stochastic" (Sato online EM, the paper's minibatch training) or
      "full" (exact M-step from the whole batch -- full-batch EM when the
      batch is the dataset).
    num_microbatches: split the batch into this many scan steps; bounds
      activation memory at batch/num_microbatches rows while keeping the
      statistics exact (they are sums over data).
    donate: donate the old parameter buffers to the update.  None means
      "donate where the backend implements it" (TPU/GPU); CPU donation is a
      no-op that only produces warnings.  Donation deletes the input
      buffers -- callers that re-feed the same params pytree (benchmarks
      timing both paths, fault-tolerant loops that replay from the initial
      state) must pass donate=False.
    axis_names: mesh axes to psum statistics over (distributed E-step).
    """

    em: EMConfig = EMConfig()
    mode: str = "stochastic"  # "stochastic" | "full"
    num_microbatches: int = 1
    donate: Optional[bool] = None
    axis_names: Optional[Sequence[str]] = None
    scan_microbatches: Optional[bool] = None
    """None: scan only above ``SCAN_UNROLL_MAX`` microbatches (measured
    small-arch crossover); True/False force the lowering either way."""
    health: Optional[bool] = None
    """Emit the device-side health vector (``repro.obs.health``) as a third
    step output.  None defers to the model's ``health`` knob (which itself
    defers to ``REPRO_HEALTH``); the resolved flag is part of the compiled
    step's registry key, so toggling it selects a different cached program
    instead of recompiling an existing one."""


def _split_microbatches(x: jax.Array, num_microbatches: int) -> jax.Array:
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(
            f"batch {b} not divisible into {num_microbatches} microbatches"
        )
    return x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])


def _resolve_scan(scan: Optional[bool], num_microbatches: int) -> bool:
    if scan is None:
        return num_microbatches > SCAN_UNROLL_MAX
    return bool(scan)


def microbatched_em_statistics(
    model: EiNet,
    params: Dict[str, Any],
    x: jax.Array,
    num_microbatches: int = 1,
    axis_names: Optional[Sequence[str]] = None,
    scan: Optional[bool] = None,
) -> Dict[str, Any]:
    """E-step statistics for ``x``, accumulated over microbatches in ONE
    compiled program.

    Same totals as the Python-loop ``accumulate_statistics`` pattern
    (statistics are sums over data).  The accumulation lowers as a
    ``lax.scan`` -- body (leaf pass, forward, backward, statistic add)
    compiled once, running accumulator kept on-device -- except at
    ``num_microbatches <= SCAN_UNROLL_MAX`` (measured crossover; see its
    comment) where the loop is unrolled into the program.  ``scan``
    overrides the threshold when not None.  Both lowerings add identical
    terms in identical order; totals agree to float32 roundoff.
    """
    if num_microbatches == 1:
        return em_statistics(model, params, x, axis_names)
    xm = _split_microbatches(x, num_microbatches)

    def body(acc, xb):
        # accumulate locally; the cross-shard psum runs ONCE on the totals
        # below, not once per microbatch (statistics are plain sums, so the
        # result is identical at 1/num_microbatches the collective traffic)
        new = em_statistics(model, params, xb, axis_names=None)
        return accumulate_statistics(acc, new), None

    if _resolve_scan(scan, num_microbatches):
        acc, _ = jax.lax.scan(body, zeros_like_statistics(model, params), xm)
    else:
        acc = zeros_like_statistics(model, params)
        for i in range(num_microbatches):
            acc, _ = body(acc, xm[i])
    return psum_statistics(acc, axis_names)


def _probe_slice(x: jax.Array, num_microbatches: int) -> jax.Array:
    """The (static) subbatch the dedicated health forward runs on: the full
    batch at one microbatch (XLA CSE merges the probe with the E-step's
    primal forward -- the scan body can't leak intermediates, so at more
    microbatches the probe re-runs one bounded forward instead)."""
    return x[: x.shape[0] // max(num_microbatches, 1)]


def em_update_microbatched(
    model: EiNet,
    params: Dict[str, Any],
    x: jax.Array,
    cfg: EMConfig = EMConfig(),
    num_microbatches: int = 1,
    axis_names: Optional[Sequence[str]] = None,
    scan: Optional[bool] = None,
    health: bool = False,
):
    """One full EM update (monotone on the batch), microbatch-accumulated.

    Returns (new_params, mean log-likelihood), plus the packed health vector
    (``repro.obs.health``) as a third element when ``health``.
    """
    stats = microbatched_em_statistics(
        model, params, x, num_microbatches, axis_names, scan
    )
    new = m_step(model, stats, cfg)
    ll = stats["ll"] / stats["count"]
    if not health:
        return new, ll
    hv = health_lib.health_vector(
        model, params, _probe_slice(x, num_microbatches), stats, new
    )
    return new, ll, hv


def stochastic_em_update_microbatched(
    model: EiNet,
    params: Dict[str, Any],
    x: jax.Array,
    cfg: EMConfig = EMConfig(),
    num_microbatches: int = 1,
    axis_names: Optional[Sequence[str]] = None,
    scan: Optional[bool] = None,
    health: bool = False,
):
    """Sato online EM (Eqs. 8/9) with microbatch-accumulated statistics."""
    stats = microbatched_em_statistics(
        model, params, x, num_microbatches, axis_names, scan
    )
    mini = m_step(model, stats, cfg)
    new = blend_params(model, params, mini, cfg.step_size)
    ll = stats["ll"] / stats["count"]
    if not health:
        return new, ll
    # entropy/clamp slots monitor the params the NEXT step will run on,
    # i.e. the blended ones
    hv = health_lib.health_vector(
        model, params, _probe_slice(x, num_microbatches), stats, new
    )
    return new, ll, hv


def _resolve_donate(donate: Optional[bool]) -> bool:
    if donate is None:
        return jax.default_backend() in ("tpu", "gpu")
    return bool(donate)


def _step_key(cfg: TrainConfig, donate: bool, tag: str,
              health: bool = False) -> tuple:
    """Registry key for one jitted training step: the step kind + every
    config field that changes the compiled program."""
    return (
        tag, cfg.mode, cfg.num_microbatches,
        _resolve_scan(cfg.scan_microbatches, cfg.num_microbatches),
        tuple(cfg.axis_names) if cfg.axis_names else None,
        cfg.em, donate, health,
    )


def _resolve_step_health(model: EiNet, cfg: TrainConfig) -> bool:
    return model.health if cfg.health is None else bool(cfg.health)


def make_em_step(
    model: EiNet,
    cfg: TrainConfig = TrainConfig(),
    registry: Optional[compile_lib.ProgramRegistry] = None,
) -> Callable[[Dict[str, Any], jax.Array], Tuple[Dict[str, Any], jax.Array]]:
    """Build the jitted, donated-buffer EM update: (params, x) -> (params, ll).

    The returned callable is the training hot path: one XLA program per
    (param, batch) shape, old parameter buffers donated to the new ones.
    Steps are cached in the shared compiled-program registry
    (``repro.compile``) keyed by (model, mode/microbatches/EM config), so
    repeat calls with the same (model, cfg) return the SAME compiled callable
    -- the serve/train unification: one registry holds serving's AOT bucket
    programs and training's donated steps.

    With health telemetry resolved on (``TrainConfig.health``, else the
    model's knob) the step returns (params, ll, health_vector) instead --
    the extra output is computed inside the same compiled program.
    """
    if cfg.mode not in ("stochastic", "full"):
        raise ValueError(f"unknown mode {cfg.mode!r}; 'stochastic' or 'full'")
    update = (
        stochastic_em_update_microbatched
        if cfg.mode == "stochastic"
        else em_update_microbatched
    )
    health_on = _resolve_step_health(model, cfg)

    def step(params, x):
        return update(
            model, params, x, cfg.em, cfg.num_microbatches, cfg.axis_names,
            cfg.scan_microbatches, health=health_on,
        )

    donate_flag = _resolve_donate(cfg.donate)
    donate = (0,) if donate_flag else ()
    reg = registry if registry is not None else compile_lib.REGISTRY
    return reg.jit(
        model, _step_key(cfg, donate_flag, "em_step", health_on), step,
        donate_argnums=donate,
    )


def make_sharded_em_step(
    model: EiNet,
    cfg: TrainConfig,
    mesh,
) -> Callable[[Dict[str, Any], jax.Array], Tuple[Dict[str, Any], jax.Array]]:
    """The multi-host form of :func:`make_em_step`: shard_map over the data
    axes with the cross-shard statistics reduction made EXPLICIT.

    The batch is split over the mesh's data axes (``cfg.axis_names``,
    defaulting to every DP axis present); each shard computes its local
    scan-accumulated E-step statistics, ``psum``s the totals over
    ``axis_names`` (one collective on the statistics, not the activations --
    structurally a gradient all-reduce, per DESIGN.md §2), and every shard
    then runs the identical M-step/blend on identical totals, so the
    returned params are replicated by construction.

    Inside the manually-partitioned body the logical-axis rule table is
    disabled (``use_rules({})``): GSPMD constraints don't apply to manual
    axes, and the psum already fixes the only layout decision that matters.

    Health telemetry is NOT supported on this path (the vector would need
    its own replication spec for no operational win -- the single-shard
    probe in ``launch.train`` covers the same failure modes); the sharded
    step always returns the 2-tuple.
    """
    if cfg.mode not in ("stochastic", "full"):
        raise ValueError(f"unknown mode {cfg.mode!r}; 'stochastic' or 'full'")
    axes = tuple(cfg.axis_names) if cfg.axis_names else tuple(
        a for a in ("pod", "data") if a in mesh.shape
    )
    if not axes:
        raise ValueError(
            f"mesh {dict(mesh.shape)} has no data axis to shard the EM "
            "batch over; use make_em_step for single-shard training"
        )
    update = (
        stochastic_em_update_microbatched
        if cfg.mode == "stochastic"
        else em_update_microbatched
    )
    from jax.sharding import PartitionSpec as P

    from repro.dist import sharding as shlib

    def local(params, x):
        with shlib.use_rules({}):
            return update(
                model, params, x, cfg.em, cfg.num_microbatches, axes,
                cfg.scan_microbatches,
            )

    sharded = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axes if len(axes) > 1 else axes[0])),
        out_specs=(P(), P()),
        # psum'd statistics make the outputs replicated; rep-tracking can't
        # see through the update's tree_map, so assert it ourselves (tests)
        check_vma=False,
    )
    donate_flag = _resolve_donate(cfg.donate)
    donate = (0,) if donate_flag else ()
    return compile_lib.REGISTRY.jit(
        model, _step_key(cfg, donate_flag, "sharded_em_step") + (mesh,),
        sharded, donate_argnums=donate,
    )


def fit(
    model: EiNet,
    params: Dict[str, Any],
    batches: Any,
    cfg: TrainConfig = TrainConfig(),
    num_steps: Optional[int] = None,
    on_step: Optional[Callable[[int, float], None]] = None,
    health_policy: Optional[health_lib.HealthPolicy] = None,
) -> Tuple[Dict[str, Any], list]:
    """Convenience driver: run the compiled step over an iterable of batches.

    ``batches`` yields (B, D) arrays (or dicts with an "x" key).  Returns
    (final_params, per-step mean-LL list).  For the production loop with
    checkpoint-restart and sharded loaders, use ``repro.launch.train``.

    With health telemetry resolved on, every step's health vector feeds the
    ``train.health.*`` gauges and a :class:`repro.obs.health.HealthWatcher`
    (``health_policy`` configures it): a divergence dumps an incident bundle
    and -- under the default "abort" policy -- raises
    :class:`repro.obs.health.DivergenceError`.

    Each step runs in the spans of :func:`run_step`, then ``train.record``
    around the bookkeeping (metrics, health, ``on_step``); the four
    ``train.*`` spans carry ``step=i``.
    """
    step_fn = make_em_step(model, cfg)
    health_on = _resolve_step_health(model, cfg)
    watcher = (
        health_lib.HealthWatcher(model, health_policy) if health_on else None
    )
    lls: list = []
    for i, batch in enumerate(batches):
        if num_steps is not None and i >= num_steps:
            break
        x = batch["x"] if isinstance(batch, dict) else batch
        out, ll = run_step(step_fn, params, x, step=i)
        params = out[0]
        lls.append(ll)
        with obs.span("train.record", step=i):
            record_step(len(x), ll)
            if watcher is not None:
                health_lib.publish(model.health_spec, out[2])
                watcher.observe(i, out[2], params)
            if on_step is not None:
                on_step(i, ll)
    return params, lls


def run_step(step_fn: Callable, params: Any, x: Any,
             to_device: Callable = jnp.asarray, **args: Any):
    """One step of a training loop: copy the host batch ``x`` to the device,
    run ``step_fn(params, x)``, and wait for its mean LL (the step's second
    output).  Returns (the step's outputs, the mean LL as a float).

    The host boundaries are ``repro.obs`` spans, named alike in every loop
    (``fit``, ``launch.train``) and carrying ``args``: ``train.copy``
    around the copy, then inside ``timed("train.step")`` (the step's
    ``train.step.seconds`` metric, dispatch plus device time)
    ``train.dispatch`` around the call and ``train.sync`` around the wait.
    """
    with obs.span("train.copy", **args):
        x = to_device(x)
    with obs.timed("train.step", metric="train.step.seconds"):
        with obs.span("train.dispatch", **args):
            out = step_fn(params, x)
        with obs.span("train.sync", **args):
            ll = float(out[1])
    return out, ll


def record_step(examples: int, ll: float) -> None:
    """A step's always-on metrics: examples seen and the last mean LL."""
    obs.METRICS.counter("train.examples.count").inc(examples)
    obs.METRICS.gauge("train.ll.last").set(ll)
