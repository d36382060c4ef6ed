"""Gather-grouped execution (Poon-Domingos topologies): parity vs the
per-layer path on both impls, lane padding, saturated rows, and vmap.

The numerics contract this file pins:

  * XLA: the chained gather reference (``layers.gather_grouped_log_einsum_exp``
    with ``impl="xla"``) builds a graph IDENTICAL to the per-layer loop --
    same per-depth op on the same gathered rows, buffer concatenated
    incrementally -- so forward AND gradients are BITWISE equal (0.0).
  * Pallas (interpret on CPU): the fused kernel evaluates every cell with
    the per-layer kernel's own ops, but keeps interior lanes at the 16-pad
    (k_p) while the per-layer ops pad every K_out to 128 lanes, and
    XLA:CPU's dot associates partial sums differently for the two output
    widths -- a platform-level ulp effect, not an algorithmic one.  So
    parity is float32-ulp-scaled, per tensor, against ``1 + max|ref|``:
    forward <= FWD_ULPS (measured <= 0.61); gradients <= GRAD_ULPS where
    the reference itself wanders (measured <= 6.92: the per-layer path's
    root mixing-weight gradient differs by that much from the XLA graph's,
    because XLA:CPU reduces over the batch in differently fused programs)
    and <= 4 ulps elsewhere -- all orders of magnitude below EM step noise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.einet import EiNet
from repro.core.exponential_family import Normal
from repro.core.layers import NEG_INF
from repro.core.region_graph import poon_domingos

# (height, width, delta, K): all produce needs_buffer PD structures whose
# plan is one gather run + the per-layer root pair.  (4, 4, 1, 3) is a
# 5-depth gather run with odd K = 3 (16-lane padding inside the kernel).
PD_SMOKE_SHAPES = [
    (4, 8, 2, 4),
    (2, 8, 2, 6),
    (4, 4, 1, 3),
]


def _pair_models(h, w, delta, k, impl="xla", **kw):
    graph = poon_domingos(h, w, delta)
    ef = Normal()
    m_g = EiNet(graph, num_sums=k, exponential_family=ef, impl=impl,
                grouped=True, **kw)
    m_p = EiNet(graph, num_sums=k, exponential_family=ef, impl=impl,
                grouped=False)
    params = m_g.init(jax.random.PRNGKey(0))
    x = jnp.asarray(
        np.random.RandomState(1).randn(8, h * w).astype(np.float32)
    )
    return m_g, m_p, params, x


FWD_ULPS = 2
GRAD_ULPS = 16
F32_EPS = float(np.finfo(np.float32).eps)


def _assert_parity(g_a, g_b, impl, ulps=4):
    """XLA: bitwise.  Pallas: <= ``ulps`` float32 ulps of 1 + the largest
    entry, per tensor."""
    for la, lb in zip(jax.tree_util.tree_leaves(g_a),
                      jax.tree_util.tree_leaves(g_b)):
        if not la.size:
            continue
        diff = float(jnp.max(jnp.abs(la - lb)))
        if impl == "xla":
            assert diff == 0.0
        else:
            mag = float(jnp.max(jnp.abs(lb)))
            assert diff <= ulps * F32_EPS * (1.0 + mag), (diff, mag)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("shape", PD_SMOKE_SHAPES, ids=str)
def test_gather_forward_bitwise(shape, impl):
    m_g, m_p, params, x = _pair_models(*shape, impl=impl)
    assert m_g.grouped_active
    assert not m_p.grouped_active
    assert m_g.grouping_summary()["gather_groups"] >= 1
    out_g = m_g.forward(params, x)
    out_p = m_p.forward(params, x)
    _assert_parity(out_g, out_p, impl, FWD_ULPS)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("shape", PD_SMOKE_SHAPES, ids=str)
def test_gather_grad_parity(shape, impl):
    m_g, m_p, params, x = _pair_models(*shape, impl=impl)

    def nll(m):
        return lambda p: -jnp.sum(m.log_likelihood(p, x))

    g_g = jax.grad(nll(m_g))(params)
    g_p = jax.grad(nll(m_p))(params)
    _assert_parity(g_g, g_p, impl, GRAD_ULPS)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_gather_neg_inf_saturated_rows(impl):
    """NEG_INF-saturated leaf rows (fully-marginalized scopes) flow through
    the gather kernel's -inf padding and stabilization clamps: bitwise
    forward parity and finite gradients on both paths."""
    m_g, m_p, params, x = _pair_models(4, 8, 2, 4, impl=impl)
    lr = m_g.leaf_rows(params, x)
    # saturate one leaf rectangle: PD decompositions overlap, so siblings
    # keep the root finite while -inf rows flow through the kernel
    lr = lr.at[:, 0, :].set(NEG_INF)

    def root(m, rows):
        return m.forward_from_leaves(params["einsum"], params["mixing"],
                                     rows)

    out_g = root(m_g, lr)
    out_p = root(m_p, lr)
    assert bool(jnp.all(jnp.isfinite(out_g)))  # guard: root stayed finite
    assert float(jnp.max(jnp.abs(out_g - out_p))) == 0.0

    gr_g = jax.grad(lambda r: jnp.sum(root(m_g, r)))(lr)
    gr_p = jax.grad(lambda r: jnp.sum(root(m_p, r)))(lr)
    assert bool(jnp.all(jnp.isfinite(gr_g)))
    _assert_parity(gr_g, gr_p, impl)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_gather_mixture_stacked_components(impl):
    """The mixture trainer vmaps forward_from_leaves over stacked component
    params (repro.mixture); the gather-grouped op must be vmap-transparent
    on both impls."""
    m_g, m_p, _, x = _pair_models(2, 8, 2, 6, impl=impl)
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
    _assert_parity(out_g, out_p, impl, FWD_ULPS)


def test_gather_em_step_parity():
    """One full EM update through the gather plan matches the per-layer
    plan: the end-to-end path the trainers actually run."""
    from repro.core.em import em_update

    m_g, m_p, params, x = _pair_models(4, 8, 2, 4, impl="xla")
    p_g, _ = em_update(m_g, params, x)
    p_p, _ = em_update(m_p, params, x)
    for la, lb in zip(jax.tree_util.tree_leaves(p_g),
                      jax.tree_util.tree_leaves(p_p)):
        if la.size:
            assert float(jnp.max(jnp.abs(la - lb))) == 0.0


def test_gather_sampling_cache_path_stays_per_layer():
    """return_cache (sampling) needs every depth's activations, so it runs
    the per-layer loop even on a gather-planned model -- and still agrees
    with the cacheless gather forward."""
    m_g, _, params, x = _pair_models(4, 8, 2, 4)
    root_plain = m_g.forward(params, x)
    root_cached, cache = m_g.forward(params, x, return_cache=True)
    assert len(cache["S"]) == len(m_g.pair_specs)
    assert float(jnp.max(jnp.abs(root_plain - root_cached))) == 0.0
