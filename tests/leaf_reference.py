"""The dense leaf layer, kept as a test reference: the paper's whole
(B, D, K, R) EF tensor ``log h(x) + einsum(T(x), theta) - A(theta)`` at
highest precision, the gather of its (variable, replica) pairs and a
``segment_sum`` into leaf rows; for the statistics, the per-pair einsum of
the gathered leaf posteriors with the gathered T(x), then a unique-index
scatter into (D, K, R, |T|)."""

import jax
import jax.numpy as jnp


def dense_terms(ef, x, phi):
    """The dense tensor's three parts, each (B, D, K, R): log h(x), the
    depth-|T| contraction T(x) . theta, and A(theta)."""
    theta = ef.expectation_to_natural(phi)  # (D, K, R, T)
    t = ef.sufficient_statistics(x)  # (B, D, T)
    dot = jnp.einsum("bdt,dkrt->bdkr", t, theta,
                     precision=jax.lax.Precision.HIGHEST)
    a = ef.log_normalizer(theta)[None]
    log_h = jnp.broadcast_to(ef.log_h(x)[:, :, None, None], dot.shape)
    return log_h, dot, a


def pairs_to_rows(net, e):
    """(B, D, K, R) -> leaf rows (B, num_leaves, K): pair gather and
    segment-sum."""
    ls = net.leaf_spec
    b, d, k, r = e.shape
    e_flat = jnp.transpose(e, (1, 3, 0, 2)).reshape(d * r, b, k)
    summed = jax.ops.segment_sum(e_flat[ls.pair_var * r + ls.pair_rep],
                                 ls.pair_leaf, num_segments=ls.num_leaves)
    return jnp.transpose(summed, (1, 0, 2))


def leaf_rows(net, params, x, marg_mask):
    """Reference leaf rows and, per row, the sum of its terms' magnitudes."""
    log_h, dot, a = dense_terms(net.ef, x, params["phi"])
    e = log_h + dot - a
    theta = net.ef.expectation_to_natural(params["phi"])
    t = net.ef.sufficient_statistics(x)
    mag = (jnp.abs(log_h) + jnp.abs(a)
           + jnp.einsum("bdt,dkrt->bdkr", jnp.abs(t), jnp.abs(theta),
                        precision=jax.lax.Precision.HIGHEST))
    if marg_mask is not None:
        e = jnp.where(marg_mask[:, :, None, None], e, 0.0)
        mag = jnp.where(marg_mask[:, :, None, None], mag, 0.0)
    return pairs_to_rows(net, e), pairs_to_rows(net, mag)


def leaf_statistics(net, g_leaf, t):
    """Per-pair einsum of gathered posteriors and T(x) (``t``, (B, D, |T|)),
    then the scatter."""
    ls = net.leaf_spec
    d, k, r = net.num_vars, net.K, ls.num_replica
    g_pairs = g_leaf[:, ls.pair_leaf, :]
    t_pairs = t[:, ls.pair_var, :]
    s_phi_pairs = jnp.einsum("bpk,bpt->pkt", g_pairs, t_pairs,
                             precision=jax.lax.Precision.HIGHEST)
    s_den_pairs = jnp.sum(g_pairs, axis=0)
    flat = ls.pair_var * r + ls.pair_rep
    tdim = net.ef.num_stats
    s_phi = (jnp.zeros((d * r, k, tdim)).at[flat].set(s_phi_pairs)
             .reshape(d, r, k, tdim).swapaxes(1, 2))
    s_den = (jnp.zeros((d * r, k)).at[flat].set(s_den_pairs)
             .reshape(d, r, k).swapaxes(1, 2))
    return s_phi, s_den
