"""Expectation-Maximization via automatic differentiation (paper §3.5).

The paper's key algorithmic observation: for a log-output circuit,

    dlogP/dw_{S,N} * w_{S,N}  =  (1/P) dP/dS N  =  n_{S,N}(x)      (Eq. 6)
    dlogP/dlogL               =  (1/P) dP/dL L  =  p_L(x)

so the *entire* E-step is one ``jax.grad`` call on the batch log-likelihood,
with the sum-over-data accumulation done by autodiff itself.  The M-step is a
renormalization (sums) resp. a weighted moment average (EF leaves, Eq. 7).

Two training modes:
  * ``em_update``         -- full/minibatch statistics, exact M-step.
  * ``stochastic_em_update`` -- Sato (1999) online EM:  p <- (1-l) p + l p_mini
    (Eqs. 8/9); the paper shows this is natural-gradient SGD under the
    complete-data Fisher.

Distribution: the sufficient statistics are *sums over data*, so the
distributed E-step is a ``psum`` over the data axes -- structurally identical
to gradient all-reduce (see ``repro.dist``).  ``em_update`` takes an optional
``axis_names`` for exactly that.

This module holds the *algorithm*; the compiled training pipeline --
microbatch statistic accumulation under ``lax.scan``, donated-buffer jitted
update steps -- lives in ``repro.train`` (EXPERIMENTS.md §Perf, "compiled EM
step").
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.einet import EiNet
from repro.dist import sharding as sharding_lib
from repro.core import layers
from repro.core.layers import normalize_einsum_weights, normalize_mixing_weights


@dataclasses.dataclass(frozen=True)
class EMConfig:
    laplace_alpha: float = 1e-4  # Laplace smoothing on sum-weight statistics
    stat_floor: float = 1e-12
    step_size: float = 0.5  # lambda for stochastic EM (paper uses 0.5)


def _psum(x, axis_names):
    return jax.lax.psum(x, axis_names) if axis_names else x


def psum_statistics(stats, axis_names):
    """Sum a statistics pytree over the data axes ``axis_names`` (the
    distributed E-step's one collective), under the ``em.allreduce`` named
    scope.  No axes: ``stats`` unchanged."""
    if not axis_names:
        return stats
    with jax.named_scope("em.allreduce"):
        return jax.tree_util.tree_map(lambda a: _psum(a, axis_names), stats)


def leaf_scatter(model: EiNet, s_phi_pairs: jax.Array,
                 s_den_pairs: jax.Array):
    """Fan per-pair leaf statistics out to parameter layout: (P, K, |T|) ->
    (D, K, R, |T|) and (P, K) -> (D, K, R).

    Every (variable, replica) pair belongs to exactly one leaf, so this is a
    unique-index scatter with zero cross-shard traffic under node sharding
    (§Perf einet it.3).  THE one definition of the fan-out: the single-model
    E-step, the vmapped mixture E-step (``repro.mixture.train``) and the
    fuse-or-not microbenchmark (``benchmarks/bench_train.py``) all time and
    run this exact op.
    """
    ls = model.leaf_spec
    d, k, r = model.num_vars, model.K, ls.num_replica
    tdim = model.ef.num_stats
    flat = ls.pair_var * r + ls.pair_rep  # unique per pair entry
    s_phi = (
        jnp.zeros((d * r, k, tdim)).at[flat].set(s_phi_pairs)
        .reshape(d, r, k, tdim).swapaxes(1, 2)
    )  # (D, K, R, |T|)
    s_den = (
        jnp.zeros((d * r, k)).at[flat].set(s_den_pairs)
        .reshape(d, r, k).swapaxes(1, 2)
    )  # (D, K, R)
    return s_phi, s_den


def em_statistics(
    model: EiNet,
    params: Dict[str, Any],
    x: jax.Array,
    axis_names: Optional[Sequence[str]] = None,
) -> Dict[str, Any]:
    """E-step: expected statistics for every parameter block, via one grad call.

    Returns a dict with:
      n_einsum: list of (L, k_out, K, K)    -- sum-node statistics n_{S,N}
      n_mixing: list of (M, C, k_out)
      s_phi:    (D, K, R, |T|)              -- sum_x p_L(x) T(x)
      s_den:    (D, K, R)                   -- sum_x p_L(x)
      n_class:  (num_classes,)
      ll:       scalar mean log-likelihood (for monitoring)

    Named scopes (HLO ``op_name``, read from a profiler trace): the whole
    E-step under ``em.estep``; inside it ``einet.leaf`` (leaf EF densities
    and their segment-sum into leaf rows), the plan walk's ``plan.<kind>``
    (forward, and its backward under ``transpose(jvp(...))``),
    ``em.leaf_stats`` (leaf sufficient statistics and their scatter) and
    ``em.allreduce`` (the psum over ``axis_names``).
    """
    with jax.named_scope("em.estep"):
        stats = _em_statistics(model, params, x)
    return psum_statistics(stats, axis_names)


def _em_statistics(model: EiNet, params: Dict[str, Any],
                   x: jax.Array) -> Dict[str, Any]:
    with jax.named_scope("einet.leaf"):
        e = model.leaf_log_prob(params, x, None)
        leaf_rows = model._leaf_rows(e)  # (B, num_leaves, K)
    prior = params["class_prior"]

    def batch_ll(einsum_w, mixing_v, lr, logprior):
        root = model.forward_from_e(einsum_w, mixing_v, None, leaf_rows=lr)
        ll = jax.scipy.special.logsumexp(root + logprior[None, :], axis=-1)
        return jnp.sum(ll)

    logprior = jnp.log(prior)
    val, grads = jax.value_and_grad(batch_ll, argnums=(0, 1, 2, 3))(
        params["einsum"], params["mixing"], leaf_rows, logprior
    )
    g_einsum, g_mixing, g_leaf, g_prior = grads
    # pin the statistic tensors to the weight sharding (layer-node axis over
    # the model mesh axis): otherwise the psum over data moves the FULL
    # 2 GB-scale stat tensors per device (EXPERIMENTS.md §Perf, einet cell)
    pinned = sharding_lib.constrain_like_params(
        {"einsum": g_einsum, "mixing": g_mixing}
    )
    g_einsum, g_mixing = pinned["einsum"], pinned["mixing"]

    # sum-node statistics: n = W * dlogP/dW  (accumulated over the batch by AD)
    n_einsum = [w * g for w, g in zip(params["einsum"], g_einsum)]
    n_mixing = [v * g for v, g in zip(params["mixing"], g_mixing)]
    # leaf statistics.  We differentiate wrt the LEAF ROWS (node-sharded, no
    # cross-shard scatter in the transpose -- §Perf einet it.3) and fan the
    # leaf posteriors out to (d, k, r): every (variable, replica) pair belongs
    # to exactly one leaf, so the fan-out is a unique-index scatter.
    ls = model.leaf_spec
    cst = sharding_lib.constraint
    with jax.named_scope("em.leaf_stats"):
        t = model.ef.sufficient_statistics(x)  # (B, D, |T|)
        g_pairs = cst(g_leaf[:, ls.pair_leaf, :],
                      ("batch", "einet_nodes", None))
        t_pairs = cst(t[:, ls.pair_var, :], ("batch", "einet_nodes", None))
        s_phi_pairs = cst(jnp.einsum("bpk,bpt->pkt", g_pairs, t_pairs,
                                     precision=layers.PRECISION),
                          ("einet_nodes", None, None))
        s_den_pairs = cst(jnp.sum(g_pairs, axis=0), ("einet_nodes", None))
        s_phi, s_den = leaf_scatter(model, s_phi_pairs, s_den_pairs)
    # dlogP/dlog(prior_c) = sum_x posterior(c | x): the expected class counts
    n_class = g_prior

    return {
        "n_einsum": n_einsum,
        "n_mixing": n_mixing,
        "s_phi": s_phi,
        "s_den": s_den,
        "n_class": n_class,
        "ll": val,
        "count": jnp.asarray(x.shape[0], jnp.float32),
    }


def m_step(
    model: EiNet,
    stats: Dict[str, Any],
    cfg: EMConfig,
) -> Dict[str, Any]:
    """Exact M-step from accumulated statistics (named scope ``em.mstep``)."""
    with jax.named_scope("em.mstep"):
        return _m_step(model, stats, cfg)


def _m_step(model: EiNet, stats: Dict[str, Any],
            cfg: EMConfig) -> Dict[str, Any]:
    alpha = cfg.laplace_alpha
    einsum_w = [
        normalize_einsum_weights(n + alpha, floor=cfg.stat_floor)
        for n in stats["n_einsum"]
    ]
    mixing_v = []
    for n, spec in zip(stats["n_mixing"], model.pair_specs):
        if spec.mix_global is None:
            mixing_v.append(n)
        else:
            mask = jnp.asarray(spec.mix_mask)
            mixing_v.append(
                normalize_mixing_weights(
                    n + alpha * mask[:, :, None], mask, floor=cfg.stat_floor
                )
            )
    den = jnp.maximum(stats["s_den"], cfg.stat_floor)
    phi = stats["s_phi"] / den[..., None]
    phi = model.ef.project_phi(phi)
    prior = stats["n_class"] + alpha
    prior = prior / jnp.sum(prior)
    return {
        "phi": phi,
        "einsum": einsum_w,
        "mixing": mixing_v,
        "class_prior": prior,
    }


def em_update(
    model: EiNet,
    params: Dict[str, Any],
    x: jax.Array,
    cfg: EMConfig = EMConfig(),
    axis_names: Optional[Sequence[str]] = None,
):
    """One full EM update on a batch (monotone on that batch). Returns
    (new_params, mean_ll)."""
    stats = em_statistics(model, params, x, axis_names)
    new = m_step(model, stats, cfg)
    return new, stats["ll"] / stats["count"]


def blend_params(
    model: EiNet,
    params: Dict[str, Any],
    mini: Dict[str, Any],
    step_size: float,
) -> Dict[str, Any]:
    """Sato online-EM interpolation (Eqs. 8/9):  p <- (1-l) p + l p_mini.

    Shared by ``stochastic_em_update`` and the compiled training pipeline
    (``repro.train``), so both paths apply the identical update -- including
    the phi re-projection that keeps EF parameters in their valid domain
    after interpolation.  Under the ``em.mstep`` named scope, with the
    M-step.
    """
    lam = step_size

    def blend(old, new):
        return (1.0 - lam) * old + lam * new

    with jax.named_scope("em.mstep"):
        return {
            "phi": model.ef.project_phi(blend(params["phi"], mini["phi"])),
            "einsum": [blend(o, n)
                       for o, n in zip(params["einsum"], mini["einsum"])],
            "mixing": [blend(o, n)
                       for o, n in zip(params["mixing"], mini["mixing"])],
            "class_prior": blend(params["class_prior"], mini["class_prior"]),
        }


def stochastic_em_update(
    model: EiNet,
    params: Dict[str, Any],
    x: jax.Array,
    cfg: EMConfig = EMConfig(),
    axis_names: Optional[Sequence[str]] = None,
):
    """Sato-style online EM (Eqs. 8/9): blend minibatch M-step with step lambda."""
    mini, ll = em_update(model, params, x, cfg, axis_names)
    return blend_params(model, params, mini, cfg.step_size), ll


def accumulate_statistics(acc: Dict[str, Any], new: Dict[str, Any]) -> Dict[str, Any]:
    """Running sum of E-step statistics across minibatches (full-batch EM on
    datasets that do not fit in one device batch)."""
    return jax.tree_util.tree_map(lambda a, b: a + b, acc, new)


def zeros_like_statistics(model: EiNet, params: Dict[str, Any]) -> Dict[str, Any]:
    tdim = model.ef.num_stats
    d, k, r = params["phi"].shape[:3]
    return {
        "n_einsum": [jnp.zeros_like(w) for w in params["einsum"]],
        "n_mixing": [jnp.zeros_like(v) for v in params["mixing"]],
        "s_phi": jnp.zeros((d, k, r, tdim)),
        "s_den": jnp.zeros((d, k, r)),
        "n_class": jnp.zeros_like(params["class_prior"]),
        "ll": jnp.zeros(()),
        "count": jnp.zeros(()),
    }
