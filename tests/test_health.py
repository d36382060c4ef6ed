"""Health-telemetry tests: the three contracts the tentpole stands on.

1. **Zero-recompile**: enabling the health vector selects a different cached
   program (new registry key) but never splits the jit cache of a running
   step -- 3 health-on steps compile exactly once under the CompileSentry.
2. **Bitwise-off**: with health off, params and LL are byte-identical to a
   run of the same step built before the health code ever executed -- the
   tap sites leave the disabled graph untouched.
3. **Flight recorder**: a seeded-NaN batch produces exactly ONE incident
   bundle (metrics snapshot, schema-valid trace, health history, params)
   and aborts or continues per policy.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.sentry import CompileSentry  # noqa: F401 (fixture dep)
from repro.compile import ProgramRegistry
from repro.core import EiNet, Normal, random_binary_trees
from repro.core.region_graph import poon_domingos
from repro.obs import health as health_lib
from repro.obs.check import validate_events, validate_metrics
from repro.train import TrainConfig, make_em_step
from repro.train.pipeline import fit


def _rat_net(health=None, **kwargs):
    g = random_binary_trees(8, 2, 2, seed=0)
    net = EiNet(g, num_sums=3, exponential_family=Normal(), health=health,
                **kwargs)
    return net, net.init(jax.random.PRNGKey(0))


def _x(net, b=16, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(b, net.num_vars), jnp.float32)


# ----------------------------------------------------------------- resolve
def test_resolve_health_env(monkeypatch):
    monkeypatch.delenv("REPRO_HEALTH", raising=False)
    assert health_lib.resolve_health(None) is False
    assert health_lib.resolve_health(True) is True
    monkeypatch.setenv("REPRO_HEALTH", "1")
    assert health_lib.resolve_health(None) is True
    assert health_lib.resolve_health(False) is False  # ctor wins
    monkeypatch.setenv("REPRO_HEALTH", "off")
    assert health_lib.resolve_health(None) is False


def test_spec_matches_plan():
    net, _ = _rat_net()
    spec = net.health_spec
    assert spec.num_segments == len(net.exec_plan)
    assert spec.names[: len(health_lib.BASE_SLOTS)] == health_lib.BASE_SLOTS
    assert spec.index("ll.mean") == 0
    d = spec.to_dict(np.zeros(spec.size))
    assert set(d) == set(spec.names)


# ----------------------------------------------------- contract 1: sentry
def test_health_on_zero_extra_compiles(compile_sentry):
    """3 health-on steps = exactly 1 compile; the vector is a fused extra
    output, not a second program or a cache split."""
    net, params = _rat_net(health=True)
    x = _x(net)
    raw = make_em_step(net, TrainConfig(donate=False),
                       registry=ProgramRegistry())
    step = compile_sentry.wrap(raw, name="em_step_health")
    for _ in range(3):
        params, ll, hv = step(params, x)
    compile_sentry.assert_max_compiles(1, name="em_step_health")
    compile_sentry.assert_no_leaks()
    assert hv.shape == (net.health_spec.size,)
    assert hv.dtype == jnp.float32
    vals = net.health_spec.to_dict(np.asarray(hv))
    assert np.isfinite(vals["ll.mean"])
    assert vals["ll.nonfinite"] == 0
    assert vals["stat.nonfinite"] == 0
    assert 0.0 <= vals["seg0.sat_frac"] <= 1.0


def test_health_toggle_is_distinct_cached_program():
    """health on/off are DIFFERENT registry keys: toggling selects a cached
    program instead of recompiling the other variant."""
    net, _ = _rat_net()
    reg = ProgramRegistry()
    a = make_em_step(net, TrainConfig(health=True), registry=reg)
    b = make_em_step(net, TrainConfig(health=False), registry=reg)
    assert a is not b
    assert make_em_step(net, TrainConfig(health=True), registry=reg) is a


# -------------------------------------------------- contract 2: bitwise-off
@pytest.mark.parametrize("microbatches", [1, 4])
def test_health_off_bitwise_identical(microbatches):
    """Same step, health on vs off: the off run's params/LL are bitwise
    equal to the on run's (the extra output is computed, never fed back)."""
    net, params = _rat_net()
    x = _x(net, b=16)
    cfg = dict(donate=False, num_microbatches=microbatches)
    on = make_em_step(net, TrainConfig(health=True, **cfg),
                      registry=ProgramRegistry())
    off = make_em_step(net, TrainConfig(health=False, **cfg),
                       registry=ProgramRegistry())
    p_on, ll_on, _ = on(params, x)
    p_off, ll_off = off(params, x)
    assert np.asarray(ll_on).tobytes() == np.asarray(ll_off).tobytes()
    for a, b in zip(jax.tree_util.tree_leaves(p_on),
                    jax.tree_util.tree_leaves(p_off)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_tap_disabled_outside_collect():
    """tap_segment without a collector is a no-op -- a forward outside
    ``collect()`` must not accumulate anything."""
    net, params = _rat_net()
    net.log_likelihood(params, _x(net))  # runs the tap sites
    with health_lib.collect() as taps:
        pass
    assert taps == []


def test_pd_gather_taps():
    """Gather-topology (PD) walk: one tap per plan segment, all finite."""
    g = poon_domingos(4, 4, delta=2)
    net = EiNet(g, num_sums=3, health=True)
    params = net.init(jax.random.PRNGKey(0))
    x = _x(net, b=8)
    rows = net.leaf_rows(params, x)
    with health_lib.collect() as taps:
        net.forward_from_leaves(params["einsum"], params["mixing"], rows)
    assert len(taps) == net.health_spec.num_segments
    assert all(np.isfinite(float(t)) for t in taps)


# --------------------------------------------- contract 3: flight recorder
def _nan_batches(net, n=6, nan_from=3):
    """Finite batches, then batches with NaN rows (seeded divergence)."""
    out = []
    for i in range(n):
        x = np.random.RandomState(i).randn(16, net.num_vars).astype(
            np.float32)
        if i >= nan_from:
            x[0, 0] = np.nan
        out.append(x)
    return out


def test_incident_bundle_once_and_schema(tmp_path):
    """Seeded NaN under continue-policy: training survives, exactly one
    bundle is dumped, and every artifact in it is schema-valid."""
    net, params = _rat_net(health=True)
    policy = health_lib.HealthPolicy(
        on_incident="continue", incident_dir=str(tmp_path / "incidents"))
    _, lls = fit(net, params, _nan_batches(net),
                 TrainConfig(donate=False), health_policy=policy)
    assert len(lls) == 6  # continue-policy: the loop ran to completion
    root = tmp_path / "incidents"
    bundles = sorted(os.listdir(root))
    assert len(bundles) == 1  # max_incidents=1: one bundle, not one per step
    bundle = root / bundles[0]
    with open(bundle / "incident.json") as f:
        inc = json.load(f)
    assert inc["step"] == 3 and "non-finite" in inc["reason"]
    assert inc["health_slots"] == list(net.health_spec.names)
    with open(bundle / "trace.json") as f:
        trace = json.load(f)
    assert validate_events(trace) == []
    assert any(ev["name"] == "train.incident"
               for ev in trace["traceEvents"])
    with open(bundle / "metrics.json") as f:
        snap = json.load(f)
    # the snapshot is schema-valid EXCEPT the non-finite train gauges
    # (health slots + last-LL) -- those NaNs ARE the incident being recorded
    assert all("'train.health." in p or "'train.ll." in p
               for p in validate_metrics(snap))
    assert any(k.startswith("train.health.") for k in snap)
    with open(bundle / "health_history.json") as f:
        hist = json.load(f)
    assert hist[-1]["step"] == 3
    with np.load(bundle / "params.npz") as npz:
        assert len(npz.files) > 0


def test_abort_policy_raises(tmp_path):
    net, params = _rat_net(health=True)
    policy = health_lib.HealthPolicy(
        on_incident="abort", incident_dir=str(tmp_path / "incidents"))
    with pytest.raises(health_lib.DivergenceError, match="non-finite"):
        fit(net, params, _nan_batches(net), TrainConfig(donate=False),
            health_policy=policy)
    assert len(os.listdir(tmp_path / "incidents")) == 1


def test_watcher_relative_triggers():
    """stat-norm explosion and saturation spikes trip against the running
    median, not absolute thresholds."""
    net, _ = _rat_net()
    spec = net.health_spec
    policy = health_lib.HealthPolicy(on_incident="continue", max_incidents=0)
    w = health_lib.HealthWatcher(net, policy)
    base = {n: 0.0 for n in spec.names}
    base.update({"ll.mean": -10.0, "stat.norm.max": 1.0,
                 "stat.norm.mean": 1.0, "weight.entropy": 1.0})

    def vec(**over):
        d = dict(base, **over)
        return np.array([d[n] for n in spec.names], np.float32)

    for i in range(4):
        assert w.observe(i, vec()) is None
    assert w._check(dict(base, **{"stat.norm.max": 100.0})) is not None
    assert w._check(dict(base, **{"seg0.sat_frac": 0.9})) is not None
    assert w._check(dict(base)) is None


def test_ef_clamp_fraction_families():
    from repro.core.exponential_family import (
        Bernoulli, Binomial, Categorical, Normal)

    n = Normal(min_var=1e-6, max_var=10.0)
    phi = np.zeros((4, 1, 1, 2), np.float32)
    phi[..., 1] = 1.0  # var 1: inside bounds
    phi[0, ..., 1] = 0.0  # var 0: pinned at min_var
    assert float(n.clamp_fraction(jnp.asarray(phi))) == pytest.approx(0.25)
    b = Bernoulli()
    pb = np.full((4, 1, 1, 1), 0.5, np.float32)
    pb[0] = 0.0
    assert float(b.clamp_fraction(jnp.asarray(pb))) == pytest.approx(0.25)
    bi = Binomial(n_trials=255)
    pbi = np.full((4, 1, 1, 1), 128.0, np.float32)
    pbi[0] = 0.0
    assert float(bi.clamp_fraction(jnp.asarray(pbi))) == pytest.approx(0.25)
    c = Categorical(num_categories=4)
    pc = np.full((2, 1, 1, 4), 0.25, np.float32)
    pc[0, ..., 0] = 0.0
    assert float(c.clamp_fraction(jnp.asarray(pc))) == pytest.approx(0.125)
