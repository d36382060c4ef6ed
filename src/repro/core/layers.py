"""The einsum layer and mixing layer (paper §3.2, §3.3, Appendix B).

Everything probabilistic lives in the log-domain; the weight tensors live in
the *linear* domain.  Numerical stability comes from the paper's
log-einsum-exp trick (Eq. 4): subtract per-row maxes before ``exp`` so the
einsum contracts numbers in (0, 1], then add the maxes back after the ``log``.

``log_einsum_exp`` dispatches between a pure-XLA einsum path (used on CPU and
as the autodiff path for EM) and the fused Pallas TPU kernel in
``repro.kernels`` (used for the forward hot loop on TPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Large-negative stand-in for log(0): keeps gradients finite where jnp.inf
# would produce NaNs through max/exp.
NEG_INF = -1e30

# Precision of every float32 contraction the model makes in XLA: the einsum
# layer below, the leaf log-densities (exponential_family) and the leaf E-step
# statistics (core.em, mixture.train).  On TPU the default runs an f32 matmul
# as one bf16 pass; on a v5e that put einet-pd-svhn's leaf log-densities up
# to 0.15 nats and its per-row LL 9.1e-4 (relative) off a highest-precision
# forward.  HIGHEST keeps float32, as the Pallas kernels do, and
# chip_smoke.py phase (c) holds the forward to 1e-5 of that reference.  CPU
# ignores the setting.  Every site reads it as ``layers.PRECISION`` when it
# traces, so a reference can override it in one place.
PRECISION = jax.lax.Precision.HIGHEST


def log_einsum_exp(w: jax.Array, ln_left: jax.Array, ln_right: jax.Array,
                   impl: str = "xla") -> jax.Array:
    """Eq. (5) with the log-einsum-exp trick of Eq. (4).

    Args:
      w:        (L, K_out, K, K) linear-domain weights, normalized over (i, j).
      ln_left:  (B, L, K) log-densities of the "left" product children.
      ln_right: (B, L, K) log-densities of the "right" product children.
      impl:     "xla" | "pallas".

    Returns:
      (B, L, K_out) log-densities  log S[b,l,k] = log sum_ij W[l,k,i,j]
                                                  exp(ln_left[b,l,i])
                                                  exp(ln_right[b,l,j]).
    """
    if impl == "pallas":
        from repro.kernels import ops as _kops

        return _kops.log_einsum_exp(w, ln_left, ln_right)
    if impl == "naive":
        from repro.core.baseline import log_einsum_exp_naive

        return log_einsum_exp_naive(w, ln_left, ln_right)
    a = jnp.max(ln_left, axis=-1, keepdims=True)  # (B, L, 1)
    ap = jnp.max(ln_right, axis=-1, keepdims=True)
    # Guard fully-marginalized / degenerate rows where the max itself is -inf.
    a = jnp.maximum(a, NEG_INF)
    ap = jnp.maximum(ap, NEG_INF)
    el = jnp.exp(ln_left - a)  # in (0, 1]
    er = jnp.exp(ln_right - ap)
    s = jnp.einsum("lkij,bli,blj->blk", w, el, er, precision=PRECISION)
    return a + ap + jnp.log(s)


def grouped_log_einsum_exp(ws, x, out_block: int, block_b: int = 128,
                           impl: str = "xla"):
    """One fused execution segment: a run of consecutive CANONICAL einsum
    layers (left = rows [0, L), right = rows [L, 2L) of the layer below),
    applied bottom-up to ``x`` (B, 2 * L_first, K).

    With ``impl == "pallas"`` the whole run is ONE kernel launch
    (``repro.kernels.grouped``): intermediate log-activations live in VMEM
    and never round-trip HBM.  Other impls execute the run as the chained
    per-depth op -- computationally identical to the per-layer loop (same
    einsum per depth, same order), so grouped XLA execution is bit-exact
    against the per-layer path by construction.

    Returns (B, L_last, K_out_last).
    """
    if impl == "pallas":
        from repro.kernels import ops as _kops

        return _kops.grouped_log_einsum_exp(out_block, block_b, tuple(ws), x)
    cur = x
    for w in ws:
        half = w.shape[0]
        cur = log_einsum_exp(w, cur[:, :half], cur[:, half: 2 * half],
                             impl=impl)
    return cur


def gather_grouped_log_einsum_exp(tables, ws, vs, x, block_b: int = 128,
                                  impl: str = "xla"):
    """One fused GATHER execution segment: a run of consecutive pairs whose
    child access is a static row lookup (Poon-Domingos topologies), applied
    bottom-up to the global row buffer ``x`` (B, r_in, K).

    ``tables`` is a ``core.plan.GatherTables``: per-depth left/right child
    row ids (into the growing buffer, global numbering) plus per-depth
    mixing tables (local indices into that depth's einsum outputs).

    With ``impl == "pallas"`` the whole run is ONE kernel launch
    (``repro.kernels.grouped``): the row buffer lives in VMEM, child
    lookups are static stacks baked at trace time, and mixing layers run
    in-kernel.  Other impls execute the run as chained take-along-axis +
    per-depth ops -- the same ``log_einsum_exp`` / ``log_mix_exp`` on the
    same gathered rows, with the buffer concatenated incrementally per
    depth exactly as the per-layer loop does, so grouped XLA execution is
    bit-exact against the per-layer path FORWARD AND BACKWARD by
    construction (an identical graph accumulates identically; returning
    only the new rows and concatenating outside would re-associate the
    cross-depth cotangent sums by ulps).

    Returns (B, r_in + r_new, K): the EXTENDED row buffer -- the input rows
    followed by every new row the run emits (einsum rows then mixing rows
    per depth, in global row order).
    """
    if impl == "pallas":
        from repro.kernels import ops as _kops

        new = _kops.gather_grouped_log_einsum_exp(
            tables, block_b, tuple(ws), tuple(vs), x
        )
        return jnp.concatenate([x, new], axis=1)
    buf = x
    vi = 0
    for t in range(tables.num_depths):
        left = np.asarray(tables.left[t])
        right = np.asarray(tables.right[t])
        s = log_einsum_exp(ws[t], buf[:, left, :], buf[:, right, :],
                           impl=impl)
        piece = s
        if tables.mix_child[t] is not None:
            child = np.asarray(tables.mix_child[t])
            mask = jnp.asarray(tables.mix_mask[t], jnp.float32)
            m = log_mix_exp(vs[vi], s[:, child, :], mask)
            vi += 1
            piece = jnp.concatenate([s, m], axis=1)
        buf = jnp.concatenate([buf, piece], axis=1)
    return buf


# Floor for the stabilized sum when dividing the backward cotangent: must be
# a NORMAL float32 (XLA flushes subnormals to zero -- a 1e-38 floor becomes
# g / 0 = inf on fully-saturated rows).  Same contract as the fused
# log-einsum-exp backward kernel (kernels/log_einsum_exp.py).
_S_FLOOR = 1e-30


def _log_mix_exp_frame(v, ln, mask):
    """The mixing layer's stabilized frame: (masked ln, clamped max, exp'd
    inputs, stabilized sum).  Shared bit-exactly by the forward and the
    custom backward, which recomputes it from the residuals instead of
    letting XLA autodiff save/reconstruct intermediates."""
    lnm = jnp.where(mask[None, :, :, None] > 0, ln, NEG_INF)
    a = jnp.maximum(jnp.max(lnm, axis=2, keepdims=True), NEG_INF)  # (B,M,1,K)
    e = jnp.exp(lnm - a)  # (B, M, C, K)
    s = jnp.sum(v[None] * e, axis=2)  # (B, M, K)
    return a, e, s


@jax.custom_vjp
def log_mix_exp(v: jax.Array, ln: jax.Array, mask: jax.Array) -> jax.Array:
    """Mixing layer (Appendix B): element-wise mixtures over C children.

    Args:
      v:    (M, C, K) linear-domain mixing weights, normalized over C;
            padded children carry zero weight.
      ln:   (B, M, C, K) log-densities of the C simple-sum children.
      mask: (M, C) 1.0 for real children, 0.0 for padding.

    Returns:
      (B, M, K) log-densities  log sum_c v[m,c,k] exp(ln[b,m,c,k]).

    Carries a fused custom VJP (the last op of the EM update off the XLA
    autodiff path): the backward recomputes the forward's stabilized frame
    from the (v, ln, mask) residuals -- same residual-recompute contract as
    the fused ``log_einsum_exp`` backward -- and emits both gradients

        dv[m,c,k]    = sum_b g[b,m,k] exp(ln[b,m,c,k] - a) / s
        dln[b,m,c,k] = g[b,m,k] v[m,c,k] exp(ln[b,m,c,k] - a) / s

    in one pass, with padded children explicitly zeroed (on fully
    marginalized NEG_INF rows ``exp(ln - a) = 1`` even where mask == 0, so
    masking the gradient is load-bearing, not cosmetic).
    """
    a, _, s = _log_mix_exp_frame(v, ln, mask)
    return a[:, :, 0, :] + jnp.log(s)


def log_mix_exp_ref(v: jax.Array, ln: jax.Array, mask: jax.Array) -> jax.Array:
    """The pure-XLA-autodiff reference (identical forward values): the grad
    parity oracle for the fused VJP (tests/test_kernels.py)."""
    a, _, s = _log_mix_exp_frame(v, ln, mask)
    return a[:, :, 0, :] + jnp.log(s)


def _lme_fwd(v, ln, mask):
    # residuals are the unpadded primals; the backward re-derives the frame
    # bit-exactly (cheap: one max + one exp sweep) so no forward
    # intermediate -- and no log -- needs to live in residual memory
    return log_mix_exp(v, ln, mask), (v, ln, mask)


def _lme_bwd(res, g):
    v, ln, mask = res
    _, e, s = _log_mix_exp_frame(v, ln, mask)
    ginv = g / jnp.maximum(s, _S_FLOOR)  # (B, M, K)
    gmask = mask[None, :, :, None]
    ge = ginv[:, :, None, :] * e * gmask  # (B, M, C, K), padding zeroed
    gv = jnp.sum(ge, axis=0)  # (M, C, K)
    gln = ge * v[None]
    return gv, gln, jnp.zeros_like(mask)


log_mix_exp.defvjp(_lme_fwd, _lme_bwd)


def normalize_einsum_weights(w: jax.Array, floor: float = 1e-12) -> jax.Array:
    """Project W onto the simplex over its last two axes (sum-weight constraint)."""
    w = jnp.maximum(w, floor)
    return w / jnp.sum(w, axis=(-2, -1), keepdims=True)


def normalize_mixing_weights(v: jax.Array, mask: jax.Array,
                             floor: float = 1e-12) -> jax.Array:
    """Project V onto the simplex over the child axis, respecting padding."""
    v = jnp.maximum(v, floor) * mask[:, :, None]
    return v / jnp.sum(v, axis=1, keepdims=True)
