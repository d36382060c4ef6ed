"""Vmapped multi-component EM: train C EiNets in lockstep as ONE program.

Training a mixture of EiNets is embarrassingly parallel over the component
axis -- C architecturally-identical components differ only in parameter
values, which :class:`~repro.mixture.model.EiNetMixture` stacks on a leading
axis.  This module advances all C components with a single jitted, donated
EM step (``vmap`` over the stack), in two regimes:

  * **hard** (the paper's CelebA protocol): the data is pre-partitioned by
    k-means (``repro.mixture.cluster``); each component runs the standard
    single-model EM update on ITS cluster's batch.  The step is
    ``vmap(em_update)`` over ``(params_c, x_c)`` with a stacked ``(C, B, D)``
    batch -- bitwise the same math as a Python loop of C single-model steps,
    executed as one XLA program (``benchmarks/bench_mixture.py`` measures the
    gap; the per-component parity is the benchmark's gate).
  * **soft**: full-mixture responsibility-weighted EM.  Because the mixture's
    top level routes through ``log_mix_exp`` (one mixing cell), the paper's
    EM-via-autodiff observation extends verbatim: ONE ``jax.grad`` of the
    summed mixture log-likelihood yields every component's statistics already
    weighted by its responsibilities r[b, c] = p(c | x_b), plus
    ``w * dL/dw = sum_b r[b, c]`` for the mixture weights.  No explicit
    E-step posterior pass exists anywhere.

Both regimes reuse ``repro.train``'s machinery -- scan-accumulated microbatch
statistics, the shared M-step/blend, donated buffers, and the shared
compiled-program registry (``repro.compile``) for the jitted step.

Unlike ``core.em.em_statistics`` the soft path does not pin statistics to the
weight sharding (``constrain_like_params``): the stacked component axis is
not in the rule table yet.  Mixture training is single-host for now; the
constraint is a no-op there anyway.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile as compile_lib
from repro.core.em import (
    EMConfig,
    accumulate_statistics,
    blend_params,
    leaf_statistics,
    m_step,
)
from repro.data.pipeline import ShardedLoader
from repro.mixture.cluster import cluster_order
from repro.mixture.model import EiNetMixture, _W_FLOOR
from repro.train.pipeline import (
    _resolve_donate,
    _split_microbatches,
    em_update_microbatched,
    stochastic_em_update_microbatched,
)


@dataclasses.dataclass(frozen=True)
class MixtureTrainConfig:
    """One compiled mixture EM step.

    assign: "hard" (per-cluster EM on a stacked (C, B, D) batch) or "soft"
      (responsibility-weighted full-mixture EM on a shared (B, D) batch).
    mode: "stochastic" (Sato blend, Eqs. 8/9) or "full" (exact M-step --
      monotone on the batch in soft mode).
    weight_alpha: Laplace smoothing on the mixture-weight statistics (soft
      mode; hard mode keeps the k-means cluster proportions fixed).
    donate / num_microbatches: as in ``repro.train.TrainConfig``.
    """

    em: EMConfig = EMConfig()
    assign: str = "hard"  # hard | soft
    mode: str = "stochastic"  # stochastic | full
    num_microbatches: int = 1
    weight_alpha: float = 1e-4
    donate: Optional[bool] = None


# ---------------------------------------------------------------- soft E-step
def mixture_em_statistics(
    mix: EiNetMixture, params: Dict[str, Any], x: jax.Array
) -> Dict[str, Any]:
    """Responsibility-weighted E-step statistics for every component, via one
    grad call on the MIXTURE log-likelihood.

    Returns the single-model statistics dict with a leading component axis on
    every tensor, plus ``n_weight`` (C,) = sum_b r[b, c].
    """
    model = mix.component
    comp = params["components"]
    weights = params["mixture_weights"]

    # (C, B, num_leaves, K)
    leaf_rows = jax.vmap(lambda p: model.leaf_rows(p, x))(comp)
    logprior = jnp.log(comp["class_prior"])  # (C, num_classes)

    def batch_ll(einsum_s, mixing_s, lr_s, logprior_s, w):
        def root_one(ew, mv, lrc, lp):
            root = model.forward_from_leaves(ew, mv, lrc)
            return jax.scipy.special.logsumexp(root + lp[None, :], axis=-1)

        cll = jax.vmap(root_one, out_axes=1)(
            einsum_s, mixing_s, lr_s, logprior_s
        )  # (B, C)
        return jnp.sum(mix.mix_log_likelihoods(w, cll))

    val, grads = jax.value_and_grad(batch_ll, argnums=(0, 1, 2, 3, 4))(
        comp["einsum"], comp["mixing"], leaf_rows, logprior, weights
    )
    g_einsum, g_mixing, g_leaf, g_prior, g_w = grads

    # sum-node statistics, responsibility-weighted by construction:
    # dL/dW of the routed mixture LL carries the r[b, c] factor that the
    # top-level log_mix_exp VJP distributes to each component's cotangent
    n_einsum = [w_ * g for w_, g in zip(comp["einsum"], g_einsum)]
    n_mixing = [v * g for v, g in zip(comp["mixing"], g_mixing)]

    # leaf statistics: the single-model definition (core.em.leaf_statistics),
    # vmapped over C with the batch shared
    s_phi, s_den = jax.vmap(lambda g: leaf_statistics(model, g, x))(g_leaf)
    return {
        "n_einsum": n_einsum,
        "n_mixing": n_mixing,
        "s_phi": s_phi,  # (C, D, K, R, |T|)
        "s_den": s_den,  # (C, D, K, R)
        "n_class": g_prior,  # (C, num_classes)
        "n_weight": weights * g_w,  # (C,) = sum_b r[b, c]
        "ll": val,
        "count": jnp.asarray(x.shape[0], jnp.float32),
    }


def zeros_like_mixture_statistics(
    mix: EiNetMixture, params: Dict[str, Any]
) -> Dict[str, Any]:
    comp = params["components"]
    c = mix.num_components
    d, k, r = comp["phi"].shape[1:4]
    tdim = mix.component.ef.num_stats
    return {
        "n_einsum": [jnp.zeros_like(w) for w in comp["einsum"]],
        "n_mixing": [jnp.zeros_like(v) for v in comp["mixing"]],
        "s_phi": jnp.zeros((c, d, k, r, tdim)),
        "s_den": jnp.zeros((c, d, k, r)),
        "n_class": jnp.zeros_like(comp["class_prior"]),
        "n_weight": jnp.zeros((c,)),
        "ll": jnp.zeros(()),
        "count": jnp.zeros(()),
    }


def microbatched_mixture_em_statistics(
    mix: EiNetMixture,
    params: Dict[str, Any],
    x: jax.Array,
    num_microbatches: int = 1,
) -> Dict[str, Any]:
    """Scan-accumulated soft statistics (sums over data, so microbatching is
    exact -- same contract as ``repro.train.microbatched_em_statistics``)."""
    if num_microbatches == 1:
        return mixture_em_statistics(mix, params, x)
    xm = _split_microbatches(x, num_microbatches)

    def body(acc, xb):
        new = mixture_em_statistics(mix, params, xb)
        return accumulate_statistics(acc, new), None

    acc, _ = jax.lax.scan(body, zeros_like_mixture_statistics(mix, params), xm)
    return acc


def mixture_m_step(
    mix: EiNetMixture,
    stats: Dict[str, Any],
    cfg: EMConfig,
    weight_alpha: float = 1e-4,
) -> Dict[str, Any]:
    """Per-component exact M-step (vmapped) + mixture-weight renormalize."""
    per_comp = {
        key: stats[key]
        for key in ("n_einsum", "n_mixing", "s_phi", "s_den", "n_class")
    }
    new_comp = jax.vmap(lambda st: m_step(mix.component, st, cfg))(per_comp)
    nw = stats["n_weight"] + weight_alpha
    return {"components": new_comp, "mixture_weights": nw / jnp.sum(nw)}


def mixture_em_update(
    mix: EiNetMixture,
    params: Dict[str, Any],
    x: jax.Array,
    cfg: MixtureTrainConfig = MixtureTrainConfig(assign="soft", mode="full"),
) -> Tuple[Dict[str, Any], jax.Array]:
    """One full soft-EM update (monotone on the batch).  Returns
    (new_params, mean mixture log-likelihood)."""
    stats = microbatched_mixture_em_statistics(
        mix, params, x, cfg.num_microbatches
    )
    new = mixture_m_step(mix, stats, cfg.em, cfg.weight_alpha)
    return new, stats["ll"] / stats["count"]


def stochastic_mixture_em_update(
    mix: EiNetMixture,
    params: Dict[str, Any],
    x: jax.Array,
    cfg: MixtureTrainConfig = MixtureTrainConfig(assign="soft"),
) -> Tuple[Dict[str, Any], jax.Array]:
    """Sato online soft EM: per-component blend + linear weight blend."""
    mini, ll = mixture_em_update(mix, params, x, cfg)
    lam = cfg.em.step_size
    comps = jax.vmap(
        lambda o, n: blend_params(mix.component, o, n, lam)
    )(params["components"], mini["components"])
    w = (1.0 - lam) * params["mixture_weights"] \
        + lam * mini["mixture_weights"]
    return {"components": comps, "mixture_weights": w}, ll


# ---------------------------------------------------------------- hard E-step
def hard_mixture_em_update(
    mix: EiNetMixture,
    params: Dict[str, Any],
    x_stacked: jax.Array,
    cfg: MixtureTrainConfig = MixtureTrainConfig(),
) -> Tuple[Dict[str, Any], jax.Array]:
    """Per-cluster EM: component c updates on its own batch ``x_stacked[c]``.

    ``vmap`` of the single-model update over (params_c, x_c): identical math
    to a Python loop of C ``em_update`` calls, one XLA program.  Mixture
    weights stay fixed (they are the k-means cluster proportions -- the
    stacked equal-size batches carry no size signal).  Returns
    (new_params, weight-averaged per-cluster mean LL).
    """
    if x_stacked.ndim != 3 or x_stacked.shape[0] != mix.num_components:
        raise ValueError(
            f"hard mixture EM needs a (C={mix.num_components}, B, D) stacked "
            f"batch; got {x_stacked.shape}"
        )
    update = (
        stochastic_em_update_microbatched
        if cfg.mode == "stochastic"
        else em_update_microbatched
    )

    def one(p, xc):
        return update(mix.component, p, xc, cfg.em, cfg.num_microbatches, None)

    new_comp, ll = jax.vmap(one)(params["components"], x_stacked)  # ll: (C,)
    w = params["mixture_weights"]
    return (
        {"components": new_comp, "mixture_weights": w},
        jnp.sum(w * ll) / jnp.maximum(jnp.sum(w), _W_FLOOR),
    )


# ------------------------------------------------------------- compiled step
def make_mixture_em_step(
    mix: EiNetMixture,
    cfg: MixtureTrainConfig = MixtureTrainConfig(),
    registry: Optional[compile_lib.ProgramRegistry] = None,
) -> Callable[[Dict[str, Any], jax.Array], Tuple[Dict[str, Any], jax.Array]]:
    """The jitted, donated mixture EM step: (params, x) -> (params, ll).

    ``assign="hard"`` expects a stacked (C, B, D) batch
    (:func:`stacked_cluster_loader`); ``assign="soft"`` a shared (B, D)
    batch.  Cached in the shared compiled-program registry keyed by the
    config, like ``repro.train.make_em_step``.
    """
    if cfg.assign not in ("hard", "soft"):
        raise ValueError(f"unknown assign {cfg.assign!r}; 'hard' or 'soft'")
    if cfg.mode not in ("stochastic", "full"):
        raise ValueError(f"unknown mode {cfg.mode!r}; 'stochastic' or 'full'")

    if cfg.assign == "hard":
        def step(params, x):
            return hard_mixture_em_update(mix, params, x, cfg)
    elif cfg.mode == "stochastic":
        def step(params, x):
            return stochastic_mixture_em_update(mix, params, x, cfg)
    else:
        def step(params, x):
            return mixture_em_update(mix, params, x, cfg)

    donate_flag = _resolve_donate(cfg.donate)
    reg = registry if registry is not None else compile_lib.REGISTRY
    return reg.jit(
        mix, ("mixture_em_step", cfg, donate_flag), step,
        donate_argnums=(0,) if donate_flag else (),
    )


# -------------------------------------------------------------------- loaders
def stacked_cluster_loader(
    data: np.ndarray,
    assignments: np.ndarray,
    num_clusters: int,
    per_component_batch: int,
    num_shards: int = 1,
    shard_id: int = 0,
    start_step: int = 0,
) -> ShardedLoader:
    """``ShardedLoader`` of stacked per-cluster batches {"x": (C, B, D)}.

    Component c's rows tile ITS cluster with the same contiguous
    block-mod-N scheme as ``repro.data.datasets.array_loader`` (shards
    within a step are disjoint per cluster, steps tile each cluster).
    Empty clusters fall back to tiling the whole dataset -- their mixture
    weight is ~0, so the rows only keep shapes static.
    """
    order, offsets = cluster_order(assignments, num_clusters)
    idx = [
        order[offsets[c]: offsets[c + 1]] for c in range(num_clusters)
    ]
    idx = [i if len(i) else np.arange(len(data)) for i in idx]

    def make(step: int, shard: int, n: int) -> Dict[str, np.ndarray]:
        out = np.empty(
            (num_clusters, n) + data.shape[1:], dtype=np.float32
        )
        base = (step * num_shards + shard) * n
        for c in range(num_clusters):
            rows = idx[c][(np.arange(n) + base) % len(idx[c])]
            out[c] = data[rows]
        return {"x": out}

    return ShardedLoader(
        make, per_component_batch * num_shards, num_shards=num_shards,
        shard_id=shard_id, start_step=start_step,
    )


# full-batch Lloyd below this many rows; deterministic contiguous-block
# minibatches above it (one threshold for every §4.2 entry point)
KMEANS_MINIBATCH_THRESHOLD = 8192


def prepare_mixture_training(
    mix: EiNetMixture,
    data: np.ndarray,
    seed: int = 0,
    global_batch: int = 512,
    kmeans_iters: int = 25,
) -> Tuple[Dict[str, Any], ShardedLoader, Any]:
    """THE §4.2 hard-EM setup, shared by ``launch/train.py`` and the eval
    workbench so both run the identical protocol: k-means the data
    (minibatched past :data:`KMEANS_MINIBATCH_THRESHOLD` rows), seed the
    mixture weights with the Laplace-smoothed cluster proportions, and build
    the stacked per-cluster loader with per-component batch
    ``max(min(global_batch, N) // C, 4)``.

    Returns (params, loader, KMeansResult).
    """
    from repro.mixture.cluster import kmeans

    c = mix.num_components
    km = kmeans(
        data, c, num_iters=kmeans_iters,
        batch=None if len(data) <= KMEANS_MINIBATCH_THRESHOLD
        else KMEANS_MINIBATCH_THRESHOLD,
        seed=seed,
    )
    params = mix.init(jax.random.PRNGKey(seed))
    # alpha=1.0: an empty cluster keeps (negligible) mass, so the log-domain
    # weight routing never sees an exact zero
    params["mixture_weights"] = jnp.asarray(km.weights(alpha=1.0))
    per_comp = max(min(global_batch, len(data)) // c, 4)
    loader = stacked_cluster_loader(data, km.assignments, c, per_comp)
    return params, loader, km


def fit_mixture(
    mix: EiNetMixture,
    params: Dict[str, Any],
    batches: Any,
    cfg: MixtureTrainConfig = MixtureTrainConfig(),
    num_steps: Optional[int] = None,
    on_step: Optional[Callable[[int, float], None]] = None,
) -> Tuple[Dict[str, Any], list]:
    """Run the compiled mixture step over an iterable of batches (dicts with
    an "x" key, or raw arrays).  Returns (final_params, per-step LL list)."""
    step_fn = make_mixture_em_step(mix, cfg)
    lls: list = []
    for i, batch in enumerate(batches):
        if num_steps is not None and i >= num_steps:
            break
        x = batch["x"] if isinstance(batch, dict) else batch
        params, ll = step_fn(params, jnp.asarray(x))
        lls.append(float(ll))
        if on_step is not None:
            on_step(i, lls[-1])
    return params, lls
