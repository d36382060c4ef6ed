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


def leaf_statistics(model: EiNet, g_leaf: jax.Array, x: jax.Array):
    """Leaf sufficient statistics from the leaf rows' cotangent ``g_leaf``
    (B, num_leaves, K), each leaf density's posterior p_L(x):
    s_phi (D, K, R, |T|) = sum_x p_L(x) T(x) and s_den (D, K, R) =
    sum_x p_L(x).

    Computed in the run layout (``EiNet.leaf_rows``) with the batch minor:
    one contraction over the batch per leaf, ``g_leaf`` against T(x) in run
    order, and the batch sum of ``g_leaf`` broadcast over the run.  A
    unique-index permutation then takes the run entries, tensors of
    parameter size, to (D, K, R, ...): every (variable, replica) entry
    belongs to at most one leaf (0 where none holds it), and no entry maps
    to a pad, so pads drop out.  THE one definition: the single-model
    E-step and the vmapped mixture E-step (``repro.mixture.train``) both
    run it.
    """
    cst = sharding_lib.constraint
    t = jnp.moveaxis(
        model.ef.sufficient_statistics(model.run_columns(x)), -1, -2)
    # (B, leaves, K) x (run, leaves, |T|, B) -> (leaves, K, run, |T|)
    s_phi = cst(jax.lax.dot_general(g_leaf, t, (((0,), (3,)), ((1,), (1,))),
                                    precision=layers.PRECISION),
                ("einet_nodes", None, None, None))
    s_den = cst(jnp.sum(g_leaf, axis=0), ("einet_nodes", None))  # (leaves, K)
    n, k, run, tdim = s_phi.shape
    s_phi = jnp.transpose(s_phi, (2, 0, 1, 3)).reshape(run * n, k, tdim)
    s_den = jnp.broadcast_to(s_den[None], (run, n, k)).reshape(run * n, k)
    return _to_param_layout(model, s_phi), _to_param_layout(model, s_den)


def _to_param_layout(model: EiNet, runs: jax.Array) -> jax.Array:
    """(run * num_leaves, K, ...) in run order -> (D, K, R, ...), by the
    static permutation ``leaf_spec.param_index``."""
    ls = model.leaf_spec
    padded = jnp.concatenate([runs, jnp.zeros_like(runs[:1])])
    out = padded[ls.param_index].reshape(
        (model.num_vars, ls.num_replica) + runs.shape[1:])
    return jnp.swapaxes(out, 1, 2)


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
    summed into leaf rows), the plan walk's ``plan.<kind>`` (forward, and
    its backward under ``transpose(jvp(...))``), ``em.leaf_stats`` (leaf
    sufficient statistics in parameter layout) and
    ``em.allreduce`` (the psum over ``axis_names``).
    """
    with jax.named_scope("em.estep"):
        stats = _em_statistics(model, params, x)
    return psum_statistics(stats, axis_names)


def _em_statistics(model: EiNet, params: Dict[str, Any],
                   x: jax.Array) -> Dict[str, Any]:
    with jax.named_scope("einet.leaf"):
        leaf_rows = model.leaf_rows(params, x)  # (B, num_leaves, K)
    prior = params["class_prior"]

    def batch_ll(einsum_w, mixing_v, lr, logprior):
        root = model.forward_from_leaves(einsum_w, mixing_v, lr)
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
    # cross-shard scatter in the transpose -- §Perf einet it.3): g_leaf is
    # each leaf density's posterior, contracted with T(x) per leaf.
    with jax.named_scope("em.leaf_stats"):
        s_phi, s_den = leaf_statistics(model, g_leaf, x)
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
