"""Fused log-einsum-exp Pallas TPU kernels: the paper's core op (Eq. 4/5),
forward and backward.

TPU adaptation of the paper's GPU einsum dispatch:

  * Per layer-node ``l``, the contraction ``W[l,k,i,j] el[b,i] er[b,j]`` is a
    ``(B_t, K^2) @ (K^2, K_out)`` matmul -- fed straight to the MXU.  The outer
    product ``el x er`` is formed in VMEM and never written back to HBM: the
    paper's "products are never materialized", restated one level lower in
    the memory hierarchy.
  * The stabilization (per-row maxes, 2K exps, K_out logs -- the paper's
    op-count argument vs the naive K^3-exp implementation) runs on the VPU,
    fused into the same kernel, so the op makes exactly one pass over HBM:
    read ``ln_left``/``ln_right``/``W`` tiles, write the ``(B_t, K_out)``
    output tile.
  * Grid = (L, B / B_t): layer-nodes are embarrassingly parallel; the batch is
    tiled so the working set  B_t*K^2 + K^2*K_out  floats stays within VMEM.
    K^2 and K_out are padded to lane multiples (``ops.pad_to_lanes``: K up to
    a multiple of 16 so K^2 lands on a 128 multiple, K_out to a full 128
    lane; padded ln entries are -inf = log 0, padded weights 0, so the
    contraction is exact).

Kernel layout (what Mosaic accepts, see ``tests/test_tpu_compile.py``):

  * Operands are CELL-MAJOR inside the kernel: ``(L, B, K)`` log-activations
    and ``(L, K_out, K^2)`` weights, with the cell axis squeezed out of every
    block (``None`` in the ``BlockSpec``).  Each block's last two dims are
    then ``(B_t, K)`` resp. ``(K_out, K^2)``, which satisfies the (8, 128)
    tiling rule; the public entry points keep the ``(B, L, K)`` /
    ``(L, K_out, K, K)`` ABI and transpose / reshape outside the kernel.
  * The outer product is built without a lane-merging reshape: two 0/1
    expansion matmuls (``el @ E_l``, ``er @ E_r`` with
    ``E_l[i, i*K + j] = E_r[j, i*K + j] = 1``) lay ``el[b, i] * er[b, j]``
    out flat at lane ``i*K + j``, and the same matrices (transposed) fold
    the backward's K^2-wide row sums back to K lanes.  Every in-kernel dot
    runs at HIGHEST precision, so the 0/1 expansions are exact and the
    contraction is float32 on the MXU as it is on the CPU.

The backward kernel (``log_einsum_exp_bwd_pallas``) is the EM hot path: the
paper's E-step is one ``jax.grad`` over this op (§3.5), so training spends
most of its FLOPs here.  It re-derives the forward's stabilized frame from
the saved residuals -- the *same* NEG_INF clamp on the row maxes as the
forward (frame mismatch on saturated rows was a live bug, see tests), and
the stabilized sum ``s`` recomputed with the forward's own MXU contraction
so it is bit-identical to what the forward logged.  (Reconstructing
``s = exp(out - a - a')`` from the saved output is NOT exact: float32
swallows ``log s`` whenever ``|a + a'|`` is astronomically larger, e.g. on
fully-masked NEG_INF rows, skewing every gradient of that row.)  It then
emits all three gradients in one fused pass:

  dW[l,k,ij]    = sum_b  ginv[b,k] (el x er)[b,ij]   -- a (K_out, B_t) @
                  (B_t, K^2) MXU contraction, accumulated across batch tiles
                  by revisiting the same output block (batch is the innermost,
                  sequential grid axis);
  dln via  c[b,ij] = sum_k ginv[b,k] W[l,k,ij]       -- a (B_t, K_out) @
                  (K_out, K^2) MXU contraction, then the row sums of
                  c * (el x er) over j (resp. i) give dln_left / dln_right.

where ``ginv = g / s`` is the cotangent divided by the stabilized sum.  The
outer product appears once in VMEM and feeds all three contractions; nothing
K^2-sized ever touches HBM except dW itself.

The per-cell helpers (``cell_fwd`` / ``cell_bwd``) are shared with the
depth-grouped kernels in ``grouped.py``, so every kernel evaluates a cell
with the same ops.  Validated against autodiff of ``ref.log_einsum_exp_ref``
in interpret mode (CPU) across shape sweeps -- see ``tests/test_kernels.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layers import NEG_INF
from repro.kernels.dispatch import resolve_interpret

# Floor for the stabilized sum when dividing the cotangent: s in (0, K^2] by
# construction, but fully-saturated rows can drive it to exactly 0.  Must be a
# NORMAL float32: XLA flushes subnormals to zero, so a 1e-38 floor becomes
# g / 0 = inf on saturated rows.  Any legitimate s is bounded below by the
# Laplace-floored weight of the row-argmax cell (>= 1e-12), far above this.
S_FLOOR = 1e-30

# Scoped-VMEM limit for every kernel: most of a v5e core's 128 MiB.  Mosaic's
# 16 MiB default refuses the K=64 per-layer backward and the PD gather
# backward at their planned tiles (tests/test_tpu_compile.py).
VMEM_LIMIT_BYTES = 100 * 2 ** 20

_NN = ((1,), (0,))  # (M, K) @ (K, N)
_NT = ((1,), (1,))  # (M, K) @ (N, K)^T
_TN = ((0,), (0,))  # (K, M)^T @ (K, N)


def dot(a, b, contract):
    """A 2-D MXU contraction at float32 (HIGHEST: Mosaic's default would
    round the operands to bf16, and the 0/1 expansions must be exact)."""
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def expanders(k: int):
    """(E_l, E_r), each (K, K^2) 0/1: ``E_l[i, i*K + j] = E_r[j, i*K + j] = 1``.

    Built from iotas inside the kernel (a Pallas kernel cannot capture array
    constants); ``el @ E_l`` repeats each lane K times, ``er @ E_r`` tiles the
    row K times, so their product is the flat outer product.
    """
    shape = (k, k * k)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    e_l = (col // k == row).astype(jnp.float32)
    e_r = (col % k == row).astype(jnp.float32)
    return e_l, e_r


def _frame(ln_l, ln_r, ex):
    """The op's stabilized frame for one cell: clamped row maxes, exp'd
    inputs and their flat outer product.

    The NEG_INF clamp is part of the op's definition (layers.py applies it in
    the XLA path too); forward and backward MUST share it so the backward's
    recomputed ``s`` lives in the same frame the forward emitted ``out`` in.
    """
    e_l, e_r = ex
    a = jnp.maximum(jnp.max(ln_l, axis=-1, keepdims=True), NEG_INF)
    ap = jnp.maximum(jnp.max(ln_r, axis=-1, keepdims=True), NEG_INF)
    el = jnp.exp(ln_l - a)  # (B_t, K), VPU
    er = jnp.exp(ln_r - ap)
    el_rep = dot(el, e_l, _NN)  # (B_t, K^2): el[b, i] at lane i*K + j
    er_tile = dot(er, e_r, _NN)  # (B_t, K^2): er[b, j] at lane i*K + j
    return a, ap, el, er, el_rep, er_tile


def cell_fwd(w, ln_l, ln_r, ex):
    """One cell: w (K_out, K^2), ln_l / ln_r (B_t, K) -> (B_t, K_out)."""
    a, ap, _, _, el_rep, er_tile = _frame(ln_l, ln_r, ex)
    s = dot(el_rep * er_tile, w, _NT)  # (B_t, K^2) @ (K^2, K_out), MXU
    return a + ap + jnp.log(s)


def cell_bwd(w, ln_l, ln_r, g, ex):
    """Backward of one cell: g (B_t, K_out) cotangent.

    Returns (gw (K_out, K^2), gl (B_t, K), gr (B_t, K)).
    """
    e_l, e_r = ex
    _, _, el, er, el_rep, er_tile = _frame(ln_l, ln_r, ex)
    prod = el_rep * er_tile
    # the forward's stabilized sum, recomputed with the forward's exact
    # contraction (same operands, same MXU op -> bit-identical frame)
    s = dot(prod, w, _NT)
    ginv = g / jnp.maximum(s, S_FLOOR)  # (B_t, K_out)
    gw = dot(ginv, prod, _TN)  # (K_out, B_t) @ (B_t, K^2)
    c = dot(ginv, w, _NN)  # (B_t, K_out) @ (K_out, K^2)
    gl = el * dot(c * er_tile, e_l, _NT)  # sum over j of each lane group i
    gr = er * dot(c * el_rep, e_r, _NT)  # sum over i of each lane j
    return gw, gl, gr


def pad_batch(block_b, *arrays):
    """Pad the leading batch axis of every array with zeros to a multiple of
    ``block_b``.  Zero rows are finite and harmless: the forward slices them
    off, and the backward sees zero cotangents there."""
    b = arrays[0].shape[0]
    pad_b = (-b) % block_b
    if not pad_b:
        return arrays
    return tuple(
        jnp.concatenate([x, jnp.zeros((pad_b,) + x.shape[1:], x.dtype)], 0)
        for x in arrays
    )


def cell_major(x):
    """(B, L, K) -> (L, B, K): the kernels' operand layout."""
    return jnp.transpose(x, (1, 0, 2))


def flat_weights(w):
    """(..., K_out, K, K) -> (..., K_out, K^2), row-major (i, j) -> i*K + j."""
    return w.reshape(w.shape[:-2] + (w.shape[-1] * w.shape[-1],))


def compiler_params(*semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT_BYTES
    )


def _fwd_kernel(w_ref, l_ref, r_ref, o_ref):
    ex = expanders(l_ref.shape[-1])
    o_ref[...] = cell_fwd(w_ref[...], l_ref[...], r_ref[...], ex).astype(
        o_ref.dtype
    )


def _bwd_kernel(w_ref, l_ref, r_ref, g_ref, gw_ref, gl_ref, gr_ref):
    ex = expanders(l_ref.shape[-1])
    gw, gl, gr = cell_bwd(w_ref[...], l_ref[...], r_ref[...], g_ref[...], ex)
    gl_ref[...] = gl.astype(gl_ref.dtype)
    gr_ref[...] = gr.astype(gr_ref.dtype)

    # batch tiles revisit the same (K_out, K^2) dW block: zero it on the
    # first tile, then accumulate (batch is the innermost, sequential axis)
    @pl.when(pl.program_id(1) == 0)
    def _init():
        gw_ref[...] = jnp.zeros_like(gw_ref)

    gw_ref[...] += gw.astype(gw_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def log_einsum_exp_pallas(
    w: jax.Array,
    ln_left: jax.Array,
    ln_right: jax.Array,
    block_b: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused forward kernel entry point.

    Args:
      w:        (L, K_out, K, K) linear-domain weights.
      ln_left:  (B, L, K) log-domain inputs.
      ln_right: (B, L, K).
      block_b:  batch tile (the grid's inner parallel dim).
      interpret: None defers to backend dispatch (compiled on TPU, interpret
        elsewhere); an explicit bool pins the mode (CPU validation in tests).

    Returns: (B, L, K_out) float32.
    """
    interpret = resolve_interpret(interpret)
    b, l, k = ln_left.shape
    k_out = w.shape[1]
    block_b = min(block_b, b)
    ln_left, ln_right = pad_batch(block_b, ln_left, ln_right)
    bp = ln_left.shape[0]
    act = pl.BlockSpec((None, block_b, k), lambda li, bi: (li, bi, 0))
    out = pl.pallas_call(
        _fwd_kernel,
        out_shape=jax.ShapeDtypeStruct((l, bp, k_out), jnp.float32),
        grid=(l, bp // block_b),
        in_specs=[
            pl.BlockSpec((None, k_out, k * k), lambda li, bi: (li, 0, 0)),
            act,
            act,
        ],
        out_specs=pl.BlockSpec(
            (None, block_b, k_out), lambda li, bi: (li, bi, 0)
        ),
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
        name="log_einsum_exp_fwd",
    )(flat_weights(w), cell_major(ln_left), cell_major(ln_right))
    return cell_major(out)[:b]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def log_einsum_exp_bwd_pallas(
    w: jax.Array,
    ln_left: jax.Array,
    ln_right: jax.Array,
    g: jax.Array,
    block_b: int = 128,
    interpret: Optional[bool] = None,
):
    """Fused backward kernel entry point (all three gradients in one pass).

    Args:
      w:        (L, K_out, K, K) linear-domain weights (forward residual).
      ln_left:  (B, L, K) log-domain inputs (forward residual).
      ln_right: (B, L, K).
      g:        (B, L, K_out) cotangent.
      block_b / interpret: as in the forward.

    Returns: (gw (L, K_out, K, K), gl (B, L, K), gr (B, L, K)), all float32.
    """
    interpret = resolve_interpret(interpret)
    b, l, k = ln_left.shape
    k_out = w.shape[1]
    block_b = min(block_b, b)
    ln_left, ln_right, g = pad_batch(block_b, ln_left, ln_right, g)
    bp = ln_left.shape[0]
    w_spec = pl.BlockSpec((None, k_out, k * k), lambda li, bi: (li, 0, 0))
    act = pl.BlockSpec((None, block_b, k), lambda li, bi: (li, bi, 0))
    gw, gl, gr = pl.pallas_call(
        _bwd_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((l, k_out, k * k), jnp.float32),
            jax.ShapeDtypeStruct((l, bp, k), jnp.float32),
            jax.ShapeDtypeStruct((l, bp, k), jnp.float32),
        ),
        grid=(l, bp // block_b),
        in_specs=[
            w_spec,
            act,
            act,
            pl.BlockSpec((None, block_b, k_out), lambda li, bi: (li, bi, 0)),
        ],
        out_specs=(w_spec, act, act),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="log_einsum_exp_bwd",
    )(flat_weights(w), cell_major(ln_left), cell_major(ln_right),
      cell_major(g))
    return gw.reshape(w.shape), cell_major(gl)[:b], cell_major(gr)[:b]
