"""Depth-grouped (whole-circuit fused) execution: parity vs the per-layer
path, plan/fallback behaviour, and the kernels' dispatch contract.

The tentpole contract this file pins:

  * grouped execution is the DEFAULT forward/backward for canonical (RAT)
    structures.  On the XLA path its outputs are BITWISE identical to the
    per-layer loop -- per segment, per depth, the same per-cell op in the
    same order -- and its gradients match the per-layer VJP to <= 1e-8
    (measured 0.0);
  * through the Pallas kernels (interpret mode on CPU) every cell runs the
    per-layer kernel's own ops, but the per-layer op pads every K_out to a
    128 lane while grouped interiors stay on the 16-lane K pad, and
    XLA:CPU's dot associates its partial sums differently for the two
    output widths.  So parity is float32-ulp-scaled, per tensor, against
    ``1 + max|reference|``: forward <= PALLAS_FWD_ULPS (measured <= 0.63),
    gradients <= PALLAS_GRAD_ULPS (measured <= 15.3: a one-ulp difference
    of a log value near -90 becomes a relative difference of 90 ulps in
    its exp, and the root weights' gradient sums such products);
  * gather/mixing (needs_buffer) structures compile to GATHER-grouped
    segments (core.plan.GatherTables) instead of falling back -- only the
    final (root) pair stays per-layer (tests/test_gather_grouped.py pins
    the numerics; this file pins the planner integration);
  * the VMEM budget splits fused segments without changing a single bit;
  * the Pallas entry points take ``interpret=None`` and resolve it through
    ``kernels.dispatch`` (never ``interpret=True`` in a public signature).
"""

import inspect
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as plan_lib
from repro.core.einet import _GROUP_BLOCK_B, EiNet
from repro.core.layers import NEG_INF
from repro.core.exponential_family import Normal
from repro.core.region_graph import random_binary_trees
from repro.kernels import dispatch, grouped
from repro.launch.cells import build_einet
from repro.configs import get_config

import leaf_reference

# fully-canonical small RAT shapes (scope collisions at smaller var counts
# break the canonical layout -- see random_binary_trees region dedup)
CANONICAL_SHAPES = [
    # (num_vars, depth, repetitions, K, num_classes)
    (64, 3, 3, 10, 1),   # odd K: 10 -> 16 lane padding inside the kernel
    (64, 4, 2, 4, 3),    # deeper chain, multi-class root
    (32, 2, 2, 6, 1),    # the smallest groupable shape (smoke-config twin)
]


def _pair_models(num_vars, depth, reps, k, nc, impl="xla", **kw):
    graph = random_binary_trees(num_vars, depth, reps, seed=0)
    ef = Normal()
    m_g = EiNet(graph, num_sums=k, num_classes=nc, exponential_family=ef,
                impl=impl, grouped=True, **kw)
    m_p = EiNet(graph, num_sums=k, num_classes=nc, exponential_family=ef,
                impl=impl, grouped=False)
    params = m_g.init(jax.random.PRNGKey(0))
    x = jnp.asarray(
        np.random.RandomState(1).randn(8, num_vars).astype(np.float32)
    )
    return m_g, m_p, params, x


PALLAS_FWD_ULPS = 2
PALLAS_GRAD_ULPS = 32
F32_EPS = float(np.finfo(np.float32).eps)


def _assert_ulps(a, b, ulps):
    """Per tensor: max|a - b| <= ulps float32 ulps of 1 + max|b|."""
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        if la.size:
            diff = float(jnp.max(jnp.abs(la - lb)))
            mag = float(jnp.max(jnp.abs(lb)))
            assert diff <= ulps * F32_EPS * (1.0 + mag), (diff, mag)


def _max_tree_diff(a, b):
    return max(
        float(jnp.max(jnp.abs(la - lb))) if la.size else 0.0
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b))
    )


@pytest.mark.parametrize("shape", CANONICAL_SHAPES, ids=str)
def test_grouped_forward_bitwise_xla(shape):
    m_g, m_p, params, x = _pair_models(*shape)
    assert m_g.grouped_active
    assert not m_p.grouped_active
    out_g = m_g.forward(params, x)
    out_p = m_p.forward(params, x)
    assert float(jnp.max(jnp.abs(out_g - out_p))) == 0.0


@pytest.mark.parametrize("shape", CANONICAL_SHAPES, ids=str)
def test_grouped_forward_bitwise_pallas(shape):
    # interpret resolves via kernels.dispatch (None -> interpret off-TPU)
    m_g, m_p, params, x = _pair_models(*shape, impl="pallas")
    assert m_g.grouped_active
    out_g = m_g.forward(params, x)
    out_p = m_p.forward(params, x)
    _assert_ulps(out_g, out_p, PALLAS_FWD_ULPS)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_grouped_grad_parity(impl):
    m_g, m_p, params, x = _pair_models(64, 3, 3, 10, 1, impl=impl)

    def nll(m):
        return lambda p: -jnp.sum(m.log_likelihood(p, x))

    g_g = jax.grad(nll(m_g))(params)
    g_p = jax.grad(nll(m_p))(params)
    if impl == "xla":
        assert _max_tree_diff(g_g, g_p) <= 1e-8
    else:
        _assert_ulps(g_g, g_p, PALLAS_GRAD_ULPS)


def test_grouped_neg_inf_saturated_rows():
    """NEG_INF-saturated leaf rows (fully-marginalized scopes) flow through
    the fused kernel's -inf padding contract: bitwise forward parity (the
    saturated rows leave this shape's contractions associated alike) and
    finite gradients on both paths, equal to PALLAS_GRAD_ULPS.  The leaf
    rows are the dense reference's, so the kernels see the same inputs
    whatever the leaf layer's rounding."""
    m_g, m_p, params, x = _pair_models(64, 3, 3, 10, 1, impl="pallas")
    lr, _ = leaf_reference.leaf_rows(m_g, params, x, None)
    lr = lr.at[:, ::3, :].set(NEG_INF)  # saturate every third leaf row

    def root(m, rows):
        out = m.forward_from_leaves(params["einsum"], params["mixing"],
                                    rows)
        return out

    out_g = root(m_g, lr)
    out_p = root(m_p, lr)
    assert float(jnp.max(jnp.abs(out_g - out_p))) == 0.0

    def loss(m):
        return lambda rows: jnp.sum(root(m, rows))

    gr_g = jax.grad(loss(m_g))(lr)
    gr_p = jax.grad(loss(m_p))(lr)
    assert bool(jnp.all(jnp.isfinite(gr_g)))
    _assert_ulps(gr_g, gr_p, PALLAS_GRAD_ULPS)


def test_needs_buffer_structures_gather_group_and_match():
    """Scope collisions at small var counts produce shared leaves ->
    non-canonical pairs -> needs_buffer: the planner now compiles these to
    gather-grouped segments (no warning, no fallback) with bitwise-identical
    results vs the per-layer loop."""
    graph = random_binary_trees(16, 3, 3, seed=0)
    ef = Normal()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        m_g = EiNet(graph, num_sums=4, exponential_family=ef, grouped=True)
    assert not any("needs_buffer" in str(w.message) for w in rec)
    assert m_g.needs_buffer
    assert m_g.grouped_active
    s = m_g.grouping_summary()
    assert s["gather_groups"] >= 1, s
    assert s["launches_grouped"] < s["launches_per_layer"], s
    m_p = EiNet(graph, num_sums=4, exponential_family=ef, grouped=False)
    params = m_g.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(2).randn(4, 16).astype(np.float32))
    assert float(jnp.max(jnp.abs(
        m_g.forward(params, x) - m_p.forward(params, x)
    ))) == 0.0


def test_vmem_budget_forces_segment_split_bitwise():
    """A VMEM budget below the 3-depth working set splits the canonical
    chain into >= 2 fused groups; the split must not change a single bit."""
    graph = random_binary_trees(64, 4, 2, seed=0)
    ef = Normal()
    whole = EiNet(graph, num_sums=4, exponential_family=ef, grouped=True)
    assert whole.grouping_summary()["fused_groups"] == 1  # whole circuit
    # largest budget that cannot fit 3 depths at the smallest tiling:
    # 2-depth groups still fit, so the greedy planner must split
    budget = plan_lib.fused_cost_bytes(
        whole.pair_specs, 0, 3, 1, min(_GROUP_BLOCK_B)
    ) - 1
    split = EiNet(graph, num_sums=4, exponential_family=ef, grouped=True,
                  vmem_budget=budget)
    summary = split.grouping_summary()
    assert summary["fused_groups"] >= 2, summary
    per_layer = EiNet(graph, num_sums=4, exponential_family=ef, grouped=False)
    params = whole.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(3).randn(8, 64).astype(np.float32))
    out_w = whole.forward(params, x)
    out_s = split.forward(params, x)
    out_p = per_layer.forward(params, x)
    assert float(jnp.max(jnp.abs(out_w - out_s))) == 0.0
    assert float(jnp.max(jnp.abs(out_s - out_p))) == 0.0
    # gradients agree across the split boundary too
    g_s = jax.grad(lambda p: -jnp.sum(split.log_likelihood(p, x)))(params)
    g_p = jax.grad(lambda p: -jnp.sum(per_layer.log_likelihood(p, x)))(params)
    assert _max_tree_diff(g_s, g_p) <= 1e-8


def test_mixture_stacked_components_bitwise():
    """The mixture trainer vmaps forward_from_leaves over stacked component
    params (repro.mixture); the grouped op must be vmap-transparent."""
    m_g, m_p, _, x = _pair_models(64, 3, 3, 6, 1)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    stacked = jax.vmap(m_g.init)(keys)

    def comp_root(m):
        def one(p):
            return m.forward_from_leaves(p["einsum"], p["mixing"],
                                         m.leaf_rows(p, x))
        return jax.vmap(one)(stacked)

    out_g = comp_root(m_g)
    out_p = comp_root(m_p)
    assert out_g.shape[0] == 3
    assert float(jnp.max(jnp.abs(out_g - out_p))) == 0.0


def test_registered_archs_grouped_parity():
    """Registered RAT archs group by default and match their per-layer
    twins bitwise (einet_rat_large is covered by BENCH_train.json -- its
    ~0.5B-weight init is too heavy for a unit test)."""
    cfg = get_config("einet_rat")
    m_g = build_einet(cfg)
    assert m_g.grouped_active
    graph = random_binary_trees(cfg.num_vars, cfg.depth, cfg.num_repetitions)
    m_p = EiNet(graph, num_sums=cfg.num_sums, num_classes=cfg.num_classes,
                exponential_family=Normal(min_var=cfg.min_var,
                                          max_var=cfg.max_var),
                grouped=False)
    params = m_g.init(jax.random.PRNGKey(0))
    x = jnp.asarray(
        np.random.RandomState(4).randn(4, cfg.num_vars).astype(np.float32)
    )
    assert float(jnp.max(jnp.abs(
        m_g.log_likelihood(params, x) - m_p.log_likelihood(params, x)
    ))) == 0.0


def test_registered_pd_arch_builds_gather_plan():
    """PD (gather topology) archs now compile to gather-grouped segments:
    strictly fewer launches than the per-layer loop, with only the final
    (root) pair left per-layer."""
    cfg = get_config("einet_pd_mnist")
    m = build_einet(cfg)
    assert m.grouped_active
    s = m.grouping_summary()
    assert s["gather_groups"] >= 1, s
    assert s["launches_grouped"] < s["launches_per_layer"], s
    # the only per-layer remainder is the root pair (non-uniform K_out)
    kinds = [seg[2] for seg in s["segments"]]
    assert kinds[-1] == "layer" and all(k == "gather" for k in kinds[:-1]), s
    assert any("final (root) pair" in r for _, r in s["fallbacks"]), s


def test_sampling_cache_path_stays_per_layer():
    """return_cache (sampling) needs every depth's activations, so it runs
    the per-layer loop even on a grouped model -- and still agrees with the
    cacheless grouped forward."""
    m_g, _, params, x = _pair_models(64, 3, 3, 6, 1)
    root_plain = m_g.forward(params, x)
    root_cached, cache = m_g.forward(params, x, return_cache=True)
    assert len(cache["S"]) == len(m_g.pair_specs)
    assert float(jnp.max(jnp.abs(root_plain - root_cached))) == 0.0


def test_kernel_signatures_resolve_interpret_via_dispatch():
    """The PR-3 bug class: no public Pallas entry point may default
    ``interpret=True`` -- the backend decision belongs to kernels.dispatch."""
    for fn in (grouped.grouped_log_einsum_exp_pallas,
               grouped.grouped_log_einsum_exp_bwd_pallas):
        sig = inspect.signature(fn)
        assert sig.parameters["interpret"].default is None, fn.__name__
    # and dispatch's resolution is the documented one: interpret off-TPU
    assert dispatch.resolve_interpret(None) == (not dispatch.on_tpu())
    assert dispatch.resolve_interpret(True) is True
    assert dispatch.resolve_interpret(False) is False


def test_grouping_summary_launch_accounting():
    """Launches drop from O(pairs) to O(segments) and the summary's segment
    list tiles the pair list exactly."""
    m_g, _, _, _ = _pair_models(64, 4, 2, 4, 1)
    s = m_g.grouping_summary()
    assert s["launches_grouped"] < s["launches_per_layer"]
    covered = []
    for start, stop, kind, _, _ in s["segments"]:
        assert kind in ("fused", "gather", "layer")
        covered.extend(range(start, stop))
    assert covered == list(range(s["num_pairs"]))
