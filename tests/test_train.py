"""Compiled EM training pipeline (repro.train) + sharded-loader regression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EMConfig,
    EiNet,
    Normal,
    accumulate_statistics,
    em_statistics,
    em_update,
    random_binary_trees,
    stochastic_em_update,
    zeros_like_statistics,
)
from repro.launch.train import einet_loader
from repro.train import (
    TrainConfig,
    em_update_microbatched,
    fit,
    make_em_step,
    microbatched_em_statistics,
    stochastic_em_update_microbatched,
)


@pytest.fixture(scope="module")
def setup():
    g = random_binary_trees(10, 2, 2, seed=0)
    net = EiNet(g, num_sums=4, exponential_family=Normal())
    params = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 10)) * 1.5 + 0.3
    return net, params, x


# ---------------------------------------------------------------- pipeline
def test_scan_statistics_match_python_loop(setup):
    """The lax.scan accumulation must total exactly what the Python-loop
    ``accumulate_statistics`` pattern totals (statistics are sums over data).

    Both sides run compiled statistics, so they differ only in how they
    accumulate: op-by-op (eager) execution rounds each ``a * b + c`` twice,
    where XLA:CPU's compiled kernels contract it to one fused multiply-add,
    a difference of rounding in the leaf densities and not of accumulation.
    """
    net, params, x = setup
    scanned = microbatched_em_statistics(net, params, x, num_microbatches=4)
    stats = jax.jit(lambda p, xb: em_statistics(net, p, xb))
    acc = zeros_like_statistics(net, params)
    for i in range(4):
        acc = accumulate_statistics(
            acc, stats(params, x[i * 16: (i + 1) * 16])
        )
    for a, b in zip(
        jax.tree_util.tree_leaves(scanned), jax.tree_util.tree_leaves(acc)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


def test_microbatched_update_matches_single_batch(setup):
    """Microbatching is an implementation detail: the EM update from 4
    microbatches must match the one-shot full-batch update."""
    net, params, x = setup
    one, ll1 = em_update(net, params, x)
    four, ll4 = em_update_microbatched(net, params, x, num_microbatches=4)
    np.testing.assert_allclose(float(ll1), float(ll4), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(one), jax.tree_util.tree_leaves(four)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6)


def test_compiled_step_matches_reference_update(setup):
    """The jitted donated-buffer step must produce the same parameters as the
    plain stochastic_em_update it compiles."""
    net, params, x = setup
    cfg = EMConfig(step_size=0.4)
    ref, ll_ref = stochastic_em_update(net, params, x, cfg)
    step = make_em_step(net, TrainConfig(em=cfg, mode="stochastic"))
    got, ll_got = step(params, x)
    np.testing.assert_allclose(float(ll_ref), float(ll_got), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(got)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_stochastic_microbatched_blend(setup):
    net, params, x = setup
    cfg = EMConfig(step_size=0.3)
    ref, _ = stochastic_em_update(net, params, x, cfg)
    got, _ = stochastic_em_update_microbatched(
        net, params, x, cfg, num_microbatches=2
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(got)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6)


def test_full_mode_step_is_monotone(setup):
    net, params, x = setup
    step = make_em_step(net, TrainConfig(mode="full", num_microbatches=2))
    p, prev = params, -np.inf
    for _ in range(6):
        p, ll = step(p, x)
        assert float(ll) >= prev - 1e-3
        prev = float(ll)


def test_fit_learns(setup):
    net, params, _ = setup
    data = jax.random.normal(jax.random.PRNGKey(7), (256, 10)) * 0.7 - 0.5
    batches = [data[i * 64: (i + 1) * 64] for i in range(4)] * 5
    p, lls = fit(net, params, batches,
                 TrainConfig(em=EMConfig(step_size=0.4)))
    assert np.mean(lls[-4:]) > np.mean(lls[:4]) + 0.5


def test_make_em_step_rejects_unknown_mode(setup):
    net, _, _ = setup
    with pytest.raises(ValueError):
        make_em_step(net, TrainConfig(mode="adam"))


def test_microbatch_divisibility_error(setup):
    net, params, x = setup
    with pytest.raises(ValueError):
        em_update_microbatched(net, params, x, num_microbatches=7)


# ------------------------------------------------------------------ loader
def test_einet_loader_shards_are_disjoint_and_cover_batch():
    """Regression: the pre-PR-3 loader ignored its shard argument, so every
    data-parallel shard trained on IDENTICAL rows."""
    data = np.arange(64, dtype=np.float32)[:, None].repeat(3, axis=1)
    num_shards, global_batch = 4, 16
    loaders = [
        einet_loader(data, global_batch, num_shards=num_shards, shard_id=sh)
        for sh in range(num_shards)
    ]
    step0 = [ld.batch_at(0)["x"] for ld in loaders]
    ids = [set(b[:, 0].astype(int).tolist()) for b in step0]
    for i in range(num_shards):
        assert len(ids[i]) == global_batch // num_shards
        for j in range(i + 1, num_shards):
            assert not ids[i] & ids[j], f"shards {i},{j} overlap: {ids[i] & ids[j]}"
    union = set().union(*ids)
    assert union == set(range(global_batch)), "step 0 must cover rows [0, 16)"
    # consecutive steps keep tiling the dataset
    step1 = set(loaders[0].batch_at(1)["x"][:, 0].astype(int).tolist())
    assert step1 == set(range(16, 20))


def test_einet_loader_explicit_shard_override():
    """batch_at(step, shard) re-points a shard (straggler remap contract)."""
    data = np.arange(32, dtype=np.float32)[:, None]
    ld = einet_loader(data, 8, num_shards=2, shard_id=0)
    own = ld.batch_at(0)["x"][:, 0]
    other = ld.batch_at(0, shard=1)["x"][:, 0]
    assert not set(own.astype(int)) & set(other.astype(int))


def test_fit_traced_is_bitwise_untraced_and_spans_each_step(setup):
    """Tracing changes nothing fit computes, and records the four host
    boundaries of every step once: ``train.copy`` before ``train.step``,
    ``train.dispatch`` then ``train.sync`` inside it, ``train.record``
    after it, each with ``step=i``."""
    from repro import obs

    net, params, _ = setup
    data = jax.random.normal(jax.random.PRNGKey(7), (192, 10))
    batches = [np.asarray(data[i * 64: (i + 1) * 64]) for i in range(3)]
    cfg = TrainConfig(donate=False)
    p0, ll0 = fit(net, params, batches, cfg)
    obs.configure(trace=True)
    obs.reset()
    try:
        p1, ll1 = fit(net, params, batches, cfg)
        events = [e for e in obs.trace_events() if e["ph"] == "X"]
    finally:
        obs.configure(trace=False)
        obs.reset()
    assert ll0 == ll1
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    steps = [e for e in events if e["name"] == "train.step"]
    assert len(steps) == len(batches)
    for i, step in enumerate(steps):
        t0, t1 = step["ts"], step["ts"] + step["dur"]
        got = {}
        for name in ("train.copy", "train.dispatch", "train.sync",
                     "train.record"):
            [got[name]] = [e for e in events if e["name"] == name
                           and e["args"] == {"step": i}]
        for name in ("train.dispatch", "train.sync"):
            e = got[name]
            assert t0 <= e["ts"] and e["ts"] + e["dur"] <= t1 + 1e-3
        assert got["train.dispatch"]["ts"] <= got["train.sync"]["ts"]
        copy, record = got["train.copy"], got["train.record"]
        assert copy["ts"] + copy["dur"] <= t0 + 1e-3
        assert t1 <= record["ts"] + 1e-3
