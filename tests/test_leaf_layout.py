"""The leaf layer in the leaf-major run layout (``EiNet.leaf_rows``,
``core.em.leaf_statistics``) against the dense formulation it replaced.

The reference is ``leaf_reference``: the paper's whole (B, D, K, R) EF
tensor at highest precision, the gather of its (variable, replica) pairs, a
``segment_sum`` into leaf rows; for the statistics, the per-pair einsum of
the gathered leaf posteriors with the gathered T(x), then a unique-index
scatter into (D, K, R, |T|).

Tolerances are in float32 ulps (eps = 2**-23) of the sum of the magnitudes
that enter each result, since both sides compute the same terms and differ
only in the order in which they add them.  A sum of n terms in two orders
differs by at most (n - 1) eps times the sum of the terms' magnitudes (each
addition rounds once, by at most eps/2 of a partial sum).  A leaf row adds
``run`` entries, and each entry itself adds |T| + 2 terms (log h, the |T|
products, -A) in an order that may differ too: ``run + |T| + 2`` ulps.  A
statistic adds B products over the batch: ``B`` ulps.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import EinetConfig
from repro.core import (
    Bernoulli,
    Categorical,
    EiNet,
    Normal,
    poon_domingos,
    random_binary_trees,
)
from repro.core.em import leaf_statistics
from repro.launch.cells import build_einet
from repro.train import TrainConfig, make_em_step

import leaf_reference

EPS = float(np.finfo(np.float32).eps)
B = 16

GRAPHS = {
    "rat": lambda: random_binary_trees(16, 2, 3, seed=0),
    "pd": lambda: poon_domingos(4, 4, delta=2),
    # 13 variables split 6/7, then 3/3 and 3/4: scopes of 3 and 4 entries
    "padded": lambda: random_binary_trees(13, 2, 2, seed=1),
}
FAMILIES = {
    "normal": Normal,
    "bernoulli": Bernoulli,
    "categorical": lambda: Categorical(num_categories=4),
}


def _data(ef, shape, seed):
    rng = np.random.RandomState(seed)
    if ef.name == "normal":
        return rng.randn(*shape).astype(np.float32)
    if ef.name == "bernoulli":
        return rng.randint(0, 2, shape).astype(np.float32)
    return rng.randint(0, ef.num_categories, shape).astype(np.float32)


def _model(graph, family):
    net = EiNet(GRAPHS[graph](), num_sums=3,
                exponential_family=FAMILIES[family]())
    params = net.init(jax.random.PRNGKey(0))
    x = jnp.asarray(_data(net.ef, (B, net.num_vars), 1))
    return net, params, x


def test_graphs_cover_uniform_and_padded_runs():
    pads = {g: EiNet(GRAPHS[g](), num_sums=2).leaf_spec.pad_share
            for g in GRAPHS}
    assert pads["rat"] == 0.0 and pads["pd"] == 0.0
    assert pads["padded"] > 0.0


@pytest.mark.parametrize("masked", [False, True], ids=["joint", "marginal"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_leaf_rows_match_dense_reference(graph, family, masked):
    """Leaf rows within ``run + |T| + 2`` ulps of the summed magnitudes."""
    net, params, x = _model(graph, family)
    mask = None
    if masked:
        mask = jnp.asarray(np.random.RandomState(2).rand(B, net.num_vars)
                           < 0.6)
    rows = jax.jit(net.leaf_rows)(params, x, mask)
    ref, mag = leaf_reference.leaf_rows(net, params, x, mask)
    assert rows.shape == (B, net.leaf_spec.num_leaves, net.K)
    run = net.leaf_spec.run_var.shape[0]
    err = np.abs(np.asarray(rows) - np.asarray(ref))
    tol = (run + net.ef.num_stats + 2) * EPS * np.asarray(mag)
    assert np.all(err <= tol), float(np.max(err - tol))
    if masked:  # fully marginalized leaves read log 1 = 0 exactly
        ls = net.leaf_spec
        none = ~np.asarray(mask)[:, ls.run_var].any(axis=1)
        assert np.all(np.asarray(rows)[none] == 0.0)


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_leaf_statistics_match_dense_reference(graph, family):
    """s_phi and s_den within ``B`` ulps of the summed magnitudes; entries
    no leaf holds are exactly 0."""
    net, _, x = _model(graph, family)
    g_leaf = jnp.asarray(np.random.RandomState(3).rand(
        B, net.leaf_spec.num_leaves, net.K).astype(np.float32))
    s_phi, s_den = jax.jit(lambda g, xb: leaf_statistics(net, g, xb))(
        g_leaf, x)
    t = net.ef.sufficient_statistics(x)
    r_phi, r_den = leaf_reference.leaf_statistics(net, g_leaf, t)
    # g >= 0 here, so the statistic of |T(x)| sums the terms' magnitudes
    m_phi, _ = leaf_reference.leaf_statistics(net, g_leaf, jnp.abs(t))
    assert s_phi.shape == r_phi.shape and s_den.shape == r_den.shape
    for got, ref, mag in ((s_phi, r_phi, m_phi), (s_den, r_den, r_den)):
        err = np.abs(np.asarray(got) - np.asarray(ref))
        assert np.all(err <= B * EPS * np.asarray(mag)), float(np.max(err))
    held = np.zeros((net.num_vars, net.leaf_spec.num_replica), bool)
    held[net.leaf_spec.pair_var, net.leaf_spec.pair_rep] = True
    assert np.all(np.asarray(s_den).swapaxes(1, 2)[~held] == 0.0)


STRUCT = {
    "rat": EinetConfig(name="leaf-rat", structure="rat", num_vars=32,
                       depth=2, num_repetitions=2, num_sums=4, batch_size=B),
    "pd": EinetConfig(name="leaf-pd", structure="pd", height=4, width=8,
                      num_channels=1, delta=2, pd_axes=("h", "w"),
                      num_sums=4, batch_size=B),
}


def _leaf_ops(hlo):
    """(opcode, element count) of every compiled op whose ``op_name`` lies
    under the leaf layer's scopes."""
    ops = []
    for line in hlo.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if not m or not re.search(r"einet\.leaf|em\.leaf_stats", m.group(1)):
            continue
        head = re.match(r"\s*(?:ROOT )?\S+ = (\w+)\[([\d,]*)\]\S* (\w+)\(",
                        line)
        if head:
            dims = [int(d) for d in head.group(2).split(",") if d]
            ops.append((head.group(3), int(np.prod(dims))))
    return ops


@pytest.mark.parametrize("arch", sorted(STRUCT))
def test_em_step_leaf_layer_has_no_scatter_or_pair_sized_gather(arch):
    """The compiled EM step's leaf layer holds no scatter, and no gather
    larger than x taken in run order (B x P elements): the (B, P, K) and
    (B, P, |T|) pair tensors of the dense layout are gone."""
    model = build_einet(STRUCT[arch])
    assert model.leaf_spec.pad_share == 0.0
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.zeros((B, model.num_vars), jnp.float32)
    hlo = make_em_step(model, TrainConfig(donate=False)).lower(
        params, x).compile().as_text()
    ops = _leaf_ops(hlo)
    assert ops, "no op carries the leaf scopes"
    assert not [op for op in ops if op[0] == "scatter"]
    pairs = B * len(model.leaf_spec.pair_var)
    assert not [op for op in ops if op[0] == "gather" and op[1] > pairs]
