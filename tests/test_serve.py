"""Serving-engine tests: queue/slot mechanics, parity of batched results with
direct model calls, bucket-padding isolation, bounded program cache."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import EiNet, Normal, random_binary_trees
from repro.dist import sharding as shlib
from repro.serve import (
    Request,
    RequestQueue,
    ServeEngine,
    SlotManager,
    direct_call,
    format_report,
    mixed_requests,
    request_key,
    run_benchmark,
)


@pytest.fixture(scope="module")
def small_net():
    g = random_binary_trees(8, 2, 2, seed=0)
    net = EiNet(g, num_sums=3, exponential_family=Normal())
    params = net.init(jax.random.PRNGKey(0))
    return net, params


# ---------------------------------------------------------------- queue/slots
def test_request_queue_fifo_and_pop_kind():
    q = RequestQueue()
    for i, kind in enumerate(["joint_ll", "mpe", "joint_ll", "sample", "mpe"]):
        q.submit(Request(i, kind))
    assert len(q) == 5
    assert q.oldest_kind() == "joint_ll"
    assert q.pending_kinds() == ["joint_ll", "mpe", "sample"]
    taken = q.pop_kind("joint_ll", limit=10)
    assert [r.req_id for r in taken] == [0, 2]
    # remaining order preserved
    assert q.oldest_kind() == "mpe"
    taken = q.pop_kind("mpe", limit=1)
    assert [r.req_id for r in taken] == [1]
    assert [r.req_id for r in q.pop_kind("sample", 5)] == [3]
    assert [r.req_id for r in q.pop_kind("mpe", 5)] == [4]
    assert len(q) == 0 and q.oldest_kind() is None


def test_slot_manager_bounds_and_release():
    s = SlotManager(3)
    leases = [s.acquire() for _ in range(3)]
    assert sorted(leases) == [0, 1, 2] and s.free == 0
    assert s.acquire() is None
    s.release(leases[0])
    assert s.free == 1
    with pytest.raises(ValueError):
        s.release(leases[0])  # double release
    assert s.acquire() == leases[0]


def test_request_key_matches_prngkey():
    for seed in (0, 1, 12345, 2**40 + 17):
        np.testing.assert_array_equal(
            np.asarray(request_key(seed)), np.asarray(jax.random.PRNGKey(seed))
        )


# -------------------------------------------------------------------- parity
def test_query_entry_point_matches_model_calls(small_net):
    net, params = small_net
    d = net.num_vars
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(5, d), jnp.float32)
    ev = jnp.asarray(rng.rand(5, d) < 0.5)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(5)])
    batch = {"x": x, "evidence_mask": ev, "query_mask": ~ev, "keys": keys}
    np.testing.assert_array_equal(
        np.asarray(net.query(params, batch, "joint_ll")),
        np.asarray(net.log_likelihood(params, x)),
    )
    np.testing.assert_array_equal(
        np.asarray(net.query(params, batch, "marginal_ll")),
        np.asarray(net.log_likelihood(params, x, ev)),
    )
    np.testing.assert_array_equal(
        np.asarray(net.query(params, batch, "conditional_ll")),
        np.asarray(net.conditional_log_likelihood(params, x, ~ev, ev)),
    )
    # per-key sampling: row i == direct batch-1 call with key i
    cs = np.asarray(net.query(params, batch, "conditional_sample"))
    for i in range(5):
        ref = net.conditional_sample(
            params, jax.random.PRNGKey(i), x[i: i + 1], ev[i: i + 1]
        )[0]
        np.testing.assert_allclose(cs[i], np.asarray(ref), atol=1e-5)
    with pytest.raises(ValueError):
        net.query(params, batch, "nope")


def test_mixed_stream_parity_with_direct_calls(small_net):
    """A shuffled heterogeneous stream through the engine must reproduce the
    direct jitted per-request calls (the acceptance contract: <= 1e-5; LL
    kinds and the discrete structure land bit-identical in practice)."""
    net, params = small_net
    reqs = mixed_requests(net.num_vars, 13, seed=2)
    engine = ServeEngine(net, params, max_batch=8)
    results = engine.run(reqs)
    assert sorted(results) == list(range(13))
    call = direct_call(net, params)
    for r in reqs:
        ref = np.asarray(call(r))
        np.testing.assert_allclose(results[r.req_id].value, ref, atol=1e-5)
        if r.kind in ("conditional_sample", "mpe"):
            # evidence rows pass through untouched
            np.testing.assert_array_equal(
                results[r.req_id].value[r.evidence_mask],
                r.x[r.evidence_mask],
            )


def test_run_benchmark_reports_parity(small_net):
    """The shared measurement reports engine-vs-direct parity both absolute
    and relative to 1 + |direct|; the relative form never exceeds the
    absolute one and is what the serve CLI gates on."""
    net, params = small_net
    report = run_benchmark(net, params, mixed_requests(net.num_vars, 8),
                           reps=1)
    assert report["parity_max_abs_diff"] <= 1e-5
    assert report["parity_max_rel_diff"] <= report["parity_max_abs_diff"]
    assert "relative to 1 + |direct|" in format_report(report)


def test_bucket_padding_never_leaks(small_net):
    """Identical streams through engines with different bucket layouts must
    return identical results: filler rows and micro-batch composition cannot
    perturb real rows (row-independent LL math + per-row sampling keys)."""
    net, params = small_net
    mix = ("joint_ll", "conditional_sample", "marginal_ll")
    reqs = mixed_requests(net.num_vars, 10, seed=3, mix=mix)
    out_small = ServeEngine(net, params, max_batch=4).run(reqs)
    out_large = ServeEngine(net, params, max_batch=16).run(reqs)
    assert ServeEngine(net, params, max_batch=16)._bucket_for(4) == 4
    for i in out_small:
        np.testing.assert_array_equal(out_small[i].value, out_large[i].value)


def test_program_cache_bounded_under_random_mix(small_net):
    """Randomized traffic must never grow the program cache beyond
    len(kinds) * len(buckets), and replaying traffic must add no compiles."""
    net, params = small_net
    kinds = ("joint_ll", "marginal_ll", "conditional_sample")
    engine = ServeEngine(net, params, max_batch=4)  # buckets (1, 2, 4)
    rng = np.random.RandomState(4)
    rid = 0
    for _ in range(12):
        wave = mixed_requests(
            net.num_vars, int(rng.randint(1, 7)), seed=rid,
            mix=tuple(rng.permutation(kinds)),
        )
        for r in wave:
            r.req_id = rid
            rid += 1
        engine.run(wave)
    bound = len(kinds) * len(engine.buckets)
    assert engine.num_programs <= bound
    assert engine.stats["compiles"] == engine.num_programs  # no retraces
    before = engine.num_programs
    engine.run(mixed_requests(net.num_vars, 12, seed=99, mix=kinds))
    assert engine.num_programs <= bound
    assert engine.num_programs == engine.stats["compiles"]
    assert engine.num_programs <= before + len(kinds)  # only new buckets


def test_engine_with_serve_rules_is_noop_on_single_device(small_net):
    """The dist degradation contract: compiling under serve_rules() on a
    single device must not change results."""
    net, params = small_net
    reqs = mixed_requests(net.num_vars, 4, seed=5, mix=("joint_ll",))
    plain = ServeEngine(net, params, max_batch=4).run(reqs)
    ruled = ServeEngine(
        net, params, max_batch=4, rules=shlib.serve_rules()
    ).run(reqs)
    for i in plain:
        np.testing.assert_array_equal(plain[i].value, ruled[i].value)
