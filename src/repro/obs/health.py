"""Device-side numerical-health telemetry for the compiled EM step.

EiNet failure modes live *inside* the compiled programs -- rows pinned at
``NEG_INF`` after a saturated ``log_einsum_exp``, EF parameters stuck at
their clamp bounds, exploding E-step statistics -- where host-side tracing
(:mod:`repro.obs.trace`) cannot see.  This module computes a fixed-shape
**health vector** as an extra output of the already-compiled training
program: every slot is a scalar reduction over intermediates XLA is already
materializing (no host callbacks, no Pallas changes), so enabling it adds
zero recompiles per step and disabling it leaves the program untouched.

Layout (:class:`HealthSpec`): a stable tuple of named slots --

  * ``ll.mean`` / ``ll.min`` / ``ll.nonfinite``  -- batch log-likelihood
    health (mean over the full batch from the E-step statistics; min and
    non-finite count over the probe microbatch);
  * ``leaf.sat_frac``    -- fraction of leaf-region rows pinned at NEG_INF;
  * ``leaf.clamp_frac``  -- fraction of EF parameters at their clamp bounds
    (:meth:`ExponentialFamily.clamp_fraction`);
  * ``weight.entropy``   -- mean sum-weight entropy (collapse detector);
  * ``stat.norm.max`` / ``stat.norm.mean`` / ``stat.nonfinite`` -- E-step
    statistic block norms and non-finite count;
  * ``seg{i}.sat_frac``  -- per execution-plan segment, the saturated-row
    fraction of that segment's ``log_einsum_exp`` output.

The per-segment slots come from **taps**: ``core/einet.py``'s plan walk
calls :func:`tap_segment` after each segment.  A tap is one thread-local
attribute read when no collector is active (the permanent cost of the
instrumentation); under :func:`collect` -- active only while the dedicated
health forward of ``train/pipeline.py`` is being traced -- it appends the
segment's saturation fraction to the health vector under construction.
The gradient/scan forwards never run under a collector, so their graphs
are byte-identical with health on or off.

Gating: the ``EiNet(health=...)`` ctor knob (``None`` defers to the
``REPRO_HEALTH`` env var), overridable per step via
``TrainConfig(health=...)``.  The fetched vector feeds ``train.health.*``
gauges (:func:`publish`) and the divergence flight recorder
(:class:`HealthWatcher` -> :mod:`repro.obs.incident`).

Import discipline: this submodule imports jax and is NOT re-exported by
``repro.obs`` (whose package root stays stdlib-only); jax-land callers
import ``repro.obs.health`` directly.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.layers import NEG_INF

# a log-space row is "saturated" when it has collapsed to the NEG_INF
# sentinel (halved so float roundoff in the stabilized frame can't unpin it)
SAT_THRESHOLD = 0.5 * NEG_INF

BASE_SLOTS: Tuple[str, ...] = (
    "ll.mean",
    "ll.min",
    "ll.nonfinite",
    "leaf.sat_frac",
    "leaf.clamp_frac",
    "weight.entropy",
    "stat.norm.max",
    "stat.norm.mean",
    "stat.nonfinite",
)


def resolve_health(value: Optional[bool]) -> bool:
    """Ctor-knob resolution: an explicit value wins, else ``REPRO_HEALTH``."""
    if value is not None:
        return bool(value)
    env = os.environ.get("REPRO_HEALTH", "").strip().lower()
    return env not in ("", "0", "false", "off", "no")


@dataclasses.dataclass(frozen=True)
class HealthSpec:
    """The fixed slot layout of one model's health vector.

    Deterministic per model (base slots + one saturation slot per execution
    segment, in plan order), so the packed vector's shape -- and therefore
    the compiled step's output signature -- never changes across steps.
    """

    names: Tuple[str, ...]

    @property
    def size(self) -> int:
        return len(self.names)

    @property
    def num_segments(self) -> int:
        return len(self.names) - len(BASE_SLOTS)

    def index(self, name: str) -> int:
        return self.names.index(name)

    def to_dict(self, vec) -> Dict[str, float]:
        return {n: float(v) for n, v in zip(self.names, vec)}


def num_segments(model) -> int:
    """Tap count of one forward pass: plan segments when the grouped walk is
    active, else one per (einsum, mixing) pair of the per-layer loop."""
    if model.grouped_active:
        return len(model.exec_plan)
    return len(model.pair_specs)


def spec_for(model) -> HealthSpec:
    return HealthSpec(BASE_SLOTS + tuple(
        f"seg{i}.sat_frac" for i in range(num_segments(model))
    ))


# ------------------------------------------------------------------- taps
_TAP = threading.local()


class _Collector:
    """Context manager arming the tap sites for one traced forward."""

    __slots__ = ("items", "_prev")

    def __init__(self):
        self.items: List[jax.Array] = []
        self._prev = None

    def __enter__(self) -> List[jax.Array]:
        self._prev = getattr(_TAP, "items", None)
        _TAP.items = self.items
        return self.items

    def __exit__(self, *exc) -> bool:
        _TAP.items = self._prev
        return False


def collect() -> _Collector:
    """Arm :func:`tap_segment` for the ``with`` body (one health forward)."""
    return _Collector()


def tap_segment(value: jax.Array) -> None:
    """Per-segment tap site (called by the ``core/einet.py`` plan walks).

    No collector active -- one thread-local attribute read, nothing added
    to the traced graph.  Collector active -- appends this segment's
    saturated-row fraction (entries pinned at NEG_INF) to the health
    vector under construction.
    """
    items = getattr(_TAP, "items", None)
    if items is None:
        return
    items.append(jnp.mean((value <= SAT_THRESHOLD).astype(jnp.float32)))


# --------------------------------------------------------- vector assembly
def saturation_fraction(value: jax.Array) -> jax.Array:
    return jnp.mean((value <= SAT_THRESHOLD).astype(jnp.float32))


def _f32(v) -> jax.Array:
    # strong float32: a weak-typed slot would change the step's output aval
    # and silently recompile (the PR 3 class_prior bug class)
    return jnp.asarray(v, jnp.float32)


def _nonfinite_count(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(jnp.sum(~jnp.isfinite(leaf)) for leaf in leaves)


def _weight_entropy(einsum_w: List[jax.Array]) -> jax.Array:
    """Mean entropy of the (K x K) child distribution of every sum node --
    near-zero entropy means the circuit has collapsed onto single children."""
    ents = []
    for w in einsum_w:
        p = w / jnp.maximum(jnp.sum(w, axis=(-2, -1), keepdims=True), 1e-38)
        ents.append(jnp.mean(
            -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-38)), axis=(-2, -1))
        ))
    return jnp.mean(jnp.stack(ents))


def health_vector(
    model,
    params: Dict[str, Any],
    probe_x: jax.Array,
    stats: Dict[str, Any],
    new_params: Dict[str, Any],
) -> jax.Array:
    """Assemble the health vector inside the compiled EM update.

    ``probe_x`` is the (sub)batch the dedicated health forward runs on --
    the full batch at one microbatch (where XLA CSE merges it with the
    E-step's primal forward), the first microbatch otherwise (the scan body
    cannot leak intermediates, so the probe re-runs one bounded forward).
    ``stats`` are the E-step statistics (full batch, exact), ``new_params``
    the post-update parameters whose entropy/clamp state we monitor.
    """
    spec = model.health_spec
    # -- dedicated health forward, tap sites armed
    leaf_rows = model.leaf_rows(params, probe_x)
    with collect() as taps:
        root = model.forward_from_leaves(
            params["einsum"], params["mixing"], leaf_rows
        )
    if len(taps) != spec.num_segments:
        raise AssertionError(
            f"health taps out of sync with the plan: got {len(taps)} "
            f"segments, spec has {spec.num_segments}"
        )
    ll_rows = jax.scipy.special.logsumexp(
        root + jnp.log(params["class_prior"])[None, :], axis=-1
    )
    # -- statistic block norms (einsum blocks + the leaf moment tensor)
    norms = jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(n))) for n in stats["n_einsum"]]
        + [jnp.sqrt(jnp.sum(jnp.square(stats["s_phi"])))]
    )
    base = {
        "ll.mean": stats["ll"] / stats["count"],
        "ll.min": jnp.min(ll_rows),
        "ll.nonfinite": jnp.sum(~jnp.isfinite(ll_rows)),
        "leaf.sat_frac": saturation_fraction(leaf_rows),
        "leaf.clamp_frac": model.ef.clamp_fraction(new_params["phi"]),
        "weight.entropy": _weight_entropy(new_params["einsum"]),
        "stat.norm.max": jnp.max(norms),
        "stat.norm.mean": jnp.mean(norms),
        "stat.nonfinite": _nonfinite_count(stats),
    }
    return jnp.stack(
        [_f32(base[n]) for n in BASE_SLOTS] + [_f32(t) for t in taps]
    )


def publish(spec: HealthSpec, vec) -> None:
    """Feed a fetched health vector into the ``train.health.*`` gauges."""
    from repro.obs.metrics import METRICS

    import numpy as np

    for name, value in zip(spec.names, np.asarray(vec)):
        METRICS.gauge(f"train.health.{name}").set(float(value))


# ------------------------------------------------- divergence flight recorder
class DivergenceError(RuntimeError):
    """Training diverged; ``bundle`` is the incident-bundle directory."""

    def __init__(self, reason: str, bundle: Optional[str]):
        super().__init__(
            f"training diverged: {reason}"
            + (f" (incident bundle: {bundle})" if bundle else "")
        )
        self.reason = reason
        self.bundle = bundle


@dataclasses.dataclass(frozen=True)
class HealthPolicy:
    """What the flight recorder does when the health vector trips.

    on_incident: "abort" raises :class:`DivergenceError` after dumping the
      bundle; "continue" dumps and keeps training.
    max_incidents: bundles dumped per run -- a persistently-NaN run under
      "continue" records ONE bundle, not one per step.
    stat_norm_factor: trip when ``stat.norm.max`` exceeds this multiple of
      its running median (needs >= ``min_history`` observations).
    sat_spike: trip when any segment's saturation fraction exceeds its
      running median by this much.
    """

    on_incident: str = "abort"  # "abort" | "continue"
    max_incidents: int = 1
    stat_norm_factor: float = 50.0
    sat_spike: float = 0.25
    min_history: int = 3
    window: int = 64
    incident_dir: str = "artifacts/incidents"


class HealthWatcher:
    """Watches the per-step health vector and dumps incident bundles.

    Host-side and cheap: one ``spec.size``-float readback per step (the
    vector was fetched anyway for the gauges).  Triggers:

      * non-finite log-likelihood or E-step statistics (immediate);
      * ``stat.norm.max`` exploding past ``stat_norm_factor`` x its running
        median;
      * any segment saturation fraction spiking ``sat_spike`` above its
        running median.

    The relative triggers compare against the run's own recent history
    (``window`` steps), so a model that *starts* saturated does not trip --
    only a step that suddenly degrades does.
    """

    def __init__(self, model, policy: Optional[HealthPolicy] = None):
        self.spec: HealthSpec = model.health_spec
        self.policy = policy or HealthPolicy()
        if self.policy.on_incident not in ("abort", "continue"):
            raise ValueError(
                f"on_incident={self.policy.on_incident!r}; "
                "'abort' or 'continue'"
            )
        self.history: "collections.deque" = collections.deque(
            maxlen=self.policy.window
        )
        self.incidents: List[str] = []
        self._sat_names = [n for n in self.spec.names
                           if n.endswith(".sat_frac")]

    def _median(self, name: str) -> Optional[float]:
        import math

        vals = sorted(h[name] for h in self.history
                      if math.isfinite(h[name]))
        if len(vals) < self.policy.min_history:
            return None
        mid = len(vals) // 2
        return (vals[mid] if len(vals) % 2
                else 0.5 * (vals[mid - 1] + vals[mid]))

    def _check(self, vals: Dict[str, float]) -> Optional[str]:
        import math

        if (vals["ll.nonfinite"] > 0 or not math.isfinite(vals["ll.mean"])
                or vals["stat.nonfinite"] > 0):
            return (
                f"non-finite values: ll.mean={vals['ll.mean']}, "
                f"ll.nonfinite={vals['ll.nonfinite']:.0f}, "
                f"stat.nonfinite={vals['stat.nonfinite']:.0f}"
            )
        med = self._median("stat.norm.max")
        if med is not None and med > 0.0 and (
                vals["stat.norm.max"] > self.policy.stat_norm_factor * med):
            return (
                f"statistic norm exploded: stat.norm.max="
                f"{vals['stat.norm.max']:.3e} vs running median {med:.3e}"
            )
        for name in self._sat_names:
            med = self._median(name)
            if med is not None and (
                    vals[name] > med + self.policy.sat_spike):
                return (
                    f"saturation spike: {name}={vals[name]:.3f} vs "
                    f"running median {med:.3f}"
                )
        return None

    def observe(self, step: int, vec, params=None) -> Optional[str]:
        """Record one step's health vector; returns the bundle path when an
        incident fired this step (and raises under the "abort" policy)."""
        import numpy as np

        vals = self.spec.to_dict(np.asarray(vec))
        reason = self._check(vals)
        self.history.append({"step": int(step), **vals})
        if reason is None:
            return None
        bundle = None
        if len(self.incidents) < self.policy.max_incidents:
            from repro.obs import incident as incident_lib

            bundle = incident_lib.dump_incident(
                self.policy.incident_dir, reason=reason, step=int(step),
                history=list(self.history), params=params, spec=self.spec,
            )
            self.incidents.append(bundle)
            print(f"[health] incident at step {step}: {reason} -> {bundle}")
        if self.policy.on_incident == "abort":
            raise DivergenceError(reason, bundle)
        return bundle
