"""Depth-grouped (whole-subcircuit) log-einsum-exp Pallas kernels.

``log_einsum_exp.py`` runs ONE (product, sum) pair per ``pallas_call``: every
depth of the circuit is a separate kernel launch and its log-activations make
a full HBM round-trip between launches.  This module fuses a RUN of
consecutive *canonical* pairs (left = rows [0, L), right = rows [L, 2L) of
the layer below -- the static-slice layout ``EiNet._canonicalize`` produces
for RAT-style structures) into a single kernel whose intermediate
activations never leave VMEM: the PyJuice-style "compile the DAG into a few
block-parallel kernels" execution model, restated for the TPU memory
hierarchy.

The key observation that makes deep fusion fit in VMEM is that a canonical
run is a forest of complete binary trees over the group's OUTPUT cells: the
set of depth-``g`` cells needed to produce output cells ``[t*s, (t+1)*s)``
is ``{c + m * L_out : c in [t*s, (t+1)*s), m < L_g / L_out}`` -- a regular
strided family.  Reshaping every operand from ``(L_g, ...)`` to
``(L_g / L_out, L_out, ...)`` turns that family into a rectangular block, so
a plain ``BlockSpec`` over the second axis tiles the whole subtree:

  * grid = (L_out / s, B / B_t): each program computes ``s`` output cells of
    the final depth for one batch tile, walking all ``G`` depths locally.
    In block coordinates every depth is still the canonical split -- inputs
    ``cur[m]`` x ``cur[m + M/2]`` -> output ``m`` for ``m < M/2``.
  * Each weight / input cell is read by EXACTLY ONE program (the trees are
    disjoint): fusion adds zero redundant HBM traffic, and shrinking ``s``
    shrinks the per-program working set proportionally, so the VMEM planner
    (``core.plan.plan_circuit``) can fuse arbitrarily wide depths by tiling
    the output cells instead of giving up.
  * Per cell the math is the per-layer kernel's own (``cell_fwd`` /
    ``cell_bwd`` from ``log_einsum_exp.py``): the same stabilization and the
    same ``(B_t, K^2) @ (K^2, K_out)`` MXU contraction.

Inside a kernel the activations are Python lists of per-cell ``(B_t, K)``
values: operands are read per cell by indexing REFS on their leading
(cell) axes -- the cell-major ``(cells, B, K)`` layout of the per-layer
kernel -- and every row selection is a static list lookup, so no loaded
value is ever indexed or reshaped (Mosaic lowers neither).

Padding contract (``ops.pad_group_for_lanes``): K is rounded up to a
multiple of 16 exactly as in ``pad_for_lanes``; INTERIOR depths pad K_out to
the same padded K (their outputs are the next depth's inputs), and padded
weight rows are zero, so padded output lanes compute ``log(0) = -inf`` --
precisely the -inf padding the next depth's inputs require.  Only the final
depth pads K_out to a full 128 lane like the per-layer kernel.

The backward kernel follows the per-layer residual-recompute VJP contract:
it re-derives every depth's activations in VMEM from the (unpadded-then-
repadded) group inputs, walks the depths in reverse emitting ``dW`` (batch
tiles accumulate by revisiting the same block; batch is the innermost,
sequential grid axis) and the input cotangent, with the stabilized sum
recomputed by the forward's exact contraction.

GATHER-GROUPED kernels (``gather_grouped_log_einsum_exp_pallas`` + bwd)
extend the same fusion to ARBITRARY child topology -- Poon-Domingos pairs
whose children are cross-depth gathers, plus interior mixing layers -- via
static permutation tables (``core.plan.GatherTables``) compiled once on
host.  The tables are baked into the kernel as COMPILE-TIME CONSTANTS:
every gather unrolls into static row selects over an in-VMEM row list, so
irregular child access costs zero dynamic indexing inside the kernel (the
PyJuice block-sparse thesis).  We deliberately do NOT use
``PrefetchScalarGridSpec`` scalar-prefetch here: prefetch feeds BlockSpec
index maps, i.e. block-LEVEL indirection across the grid, while these
gathers select rows WITHIN the single resident buffer block -- a static
unroll is both simpler and exact.  The trade-off is one specialized program
per distinct table set (fine: one circuit has a handful of segments).  Grid
is batch-only: the row buffer is irregular, so the segment is not
cell-tiled; the planner (``core.plan.gather_cost_bytes``) bounds run LENGTH
instead of out_block.  Interior depths keep K_out == K and outputs stay on
the 16-multiple K lane (never widened to 128: all depths are non-final by
construction).

Validated against autodiff of the chained XLA reference in interpret mode --
see ``tests/test_grouped.py`` and ``tests/test_gather_grouped.py`` -- and
compiled for a described TPU v5e in ``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layers import NEG_INF
from repro.kernels.dispatch import resolve_interpret
from repro.kernels.log_einsum_exp import (
    S_FLOOR,
    cell_bwd,
    cell_fwd,
    cell_major,
    compiler_params,
    expanders,
    flat_weights,
    pad_batch,
)


def _zero(refs):
    """Zero accumulator blocks on the first batch tile (batch tiles revisit
    the same dW / dV block: batch is the innermost, sequential grid axis)."""
    for r in refs:
        r[...] = jnp.zeros(r.shape, r.dtype)


def _each_cell(w_ref, body):
    """Run ``body(mi, ci)`` over one canonical depth's (M/2, s) output cells
    as a loop (one compiled cell body per depth, not one per cell)."""
    h, s = w_ref.shape[:2]

    def step(idx, carry):
        body(idx // s, idx % s)
        return carry

    jax.lax.fori_loop(0, h * s, step, 0)


def _depth_fwd(w_ref, src, dst, ex):
    """One canonical depth in block coordinates: w_ref (M/2, s, K_out, K^2),
    src (M, s, B_t, K) -> dst (M/2, s, B_t, K_out).  Left children are rows
    [0, M/2) of ``src``, right children rows [M/2, M) (the canonical
    split)."""
    h = w_ref.shape[0]

    def cell(mi, ci):
        dst[mi, ci] = cell_fwd(
            w_ref[mi, ci], src[mi, ci], src[mi + h, ci], ex
        ).astype(dst.dtype)

    _each_cell(w_ref, cell)


def _depth_bwd(w_ref, src, gout, gw_ref, gin, ex):
    """Backward of one canonical depth: accumulates dW into ``gw_ref`` and
    writes the input cotangent ``gin`` (shaped like ``src``; every input
    cell has exactly one consumer, so it is written once)."""
    h = w_ref.shape[0]

    def cell(mi, ci):
        gw, gl, gr = cell_bwd(
            w_ref[mi, ci], src[mi, ci], src[mi + h, ci], gout[mi, ci], ex
        )
        gw_ref[mi, ci] += gw.astype(gw_ref.dtype)
        gin[mi, ci] = gl.astype(gin.dtype)
        gin[mi + h, ci] = gr.astype(gin.dtype)

    _each_cell(w_ref, cell)


def _make_fwd_kernel(num_depths: int):
    def kernel(*refs):
        w_refs = refs[:num_depths]
        x_ref, o_ref = refs[num_depths], refs[num_depths + 1]
        acts = refs[num_depths + 2:]  # VMEM scratch: interior depth outputs
        ex = expanders(x_ref.shape[-1])
        srcs, dsts = (x_ref,) + acts, acts + (o_ref,)
        for g in range(num_depths):
            _depth_fwd(w_refs[g], srcs[g], dsts[g], ex)

    return kernel


def _make_bwd_kernel(num_depths: int):
    def kernel(*refs):
        w_refs = refs[:num_depths]
        x_ref, g_ref = refs[num_depths], refs[num_depths + 1]
        gw_refs = refs[num_depths + 2: 2 * num_depths + 2]
        gx_ref = refs[2 * num_depths + 2]
        # VMEM scratch: interior depth outputs, then their cotangents
        scratch = refs[2 * num_depths + 3:]
        acts, cots = scratch[: num_depths - 1], scratch[num_depths - 1:]
        ex = expanders(x_ref.shape[-1])
        # recompute every depth's activations in VMEM (residual-recompute:
        # nothing but the group inputs was saved)
        srcs = (x_ref,) + acts
        for g in range(num_depths - 1):
            _depth_fwd(w_refs[g], srcs[g], acts[g], ex)
        pl.when(pl.program_id(1) == 0)(lambda: _zero(gw_refs))
        gins, gouts = (gx_ref,) + cots, cots + (g_ref,)
        for g in reversed(range(num_depths)):
            _depth_bwd(w_refs[g], srcs[g], gouts[g], gw_refs[g], gins[g], ex)

    return kernel


def _group_geometry(ws: Sequence[jax.Array], x: jax.Array):
    """Validate the canonical-run shapes and return (G, L_out, K, K_final)."""
    g = len(ws)
    b, rows, k = x.shape
    l_out = ws[-1].shape[0]
    if rows != l_out * 2 ** g:
        raise ValueError(
            f"group input has {rows} rows; a {g}-depth canonical run over "
            f"{l_out} output cells needs {l_out * 2 ** g}"
        )
    for d, w in enumerate(ws):
        if w.shape[0] != l_out * 2 ** (g - 1 - d):
            raise ValueError(
                f"depth {d} has {w.shape[0]} cells, expected "
                f"{l_out * 2 ** (g - 1 - d)} (canonical halving)"
            )
        if w.shape[-1] != k or w.shape[-2] != k:
            raise ValueError(f"depth {d} weight K {w.shape[-2:]} != input K {k}")
        if d < g - 1 and w.shape[1] != k:
            raise ValueError(
                f"interior depth {d} K_out {w.shape[1]} != K {k}; interior "
                "outputs feed the next depth so K_out must equal K"
            )
    return g, l_out, k, ws[-1].shape[1]


def _group_operands(ws, x, l_out, out_block, block_b):
    """Cell-major operands and block specs for a canonical run.

    The input is viewed as (2^G, L_out, B, K) and depth ``d``'s weights as
    (2^(G-1-d), L_out, K_out_d, K^2); program (ti, bi) reads the subtrees of
    output cells [ti*s, (ti+1)*s) for batch tile ``bi``.  Returns
    (w_t, x_t, w_specs, act_spec, scratch) where ``act_spec(m, lanes)``
    blocks an (m, L_out, B, lanes) activation and ``scratch`` holds one VMEM
    buffer per interior depth output.
    """
    g = len(ws)
    k = x.shape[-1]
    s = out_block
    x_t = cell_major(x).reshape(2 ** g, l_out, x.shape[0], k)
    w_t = [
        flat_weights(w).reshape(2 ** (g - 1 - d), l_out, w.shape[1], k * k)
        for d, w in enumerate(ws)
    ]
    w_specs = [
        pl.BlockSpec((w.shape[0], s) + w.shape[2:],
                     lambda ti, bi: (0, ti, 0, 0))
        for w in w_t
    ]

    def act_spec(m, lanes):
        return pl.BlockSpec((m, s, block_b, lanes),
                            lambda ti, bi: (0, ti, bi, 0))

    scratch = [
        pltpu.VMEM((2 ** (g - 1 - d), s, block_b, k), jnp.float32)
        for d in range(g - 1)
    ]
    return w_t, x_t, w_specs, act_spec, scratch


@functools.partial(
    jax.jit, static_argnames=("out_block", "block_b", "interpret")
)
def grouped_log_einsum_exp_pallas(
    ws: Tuple[jax.Array, ...],
    x: jax.Array,
    out_block: int = 1,
    block_b: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused multi-depth forward: one kernel launch for a canonical run.

    Args:
      ws: per-depth linear-domain weights, input side first; depth ``d`` has
        shape (L_out * 2^(G-1-d), K_out_d, K, K) with K_out_d == K for every
        interior depth (padded per ``ops.pad_group_for_lanes``).
      x: (B, L_out * 2^G, K) log-domain inputs of the first depth (left
        children rows [0, L_0), right children rows [L_0, 2 L_0)).
      out_block: output cells per program (``s``); must divide L_out.  The
        VMEM knob: each program's working set is the s / L_out fraction of
        the whole group.
      block_b: batch tile.
      interpret: None defers to backend dispatch (compiled on TPU, interpret
        elsewhere); an explicit bool pins the mode.

    Returns: (B, L_out, K_out_final) float32.
    """
    interpret = resolve_interpret(interpret)
    g, l_out, k, k_final = _group_geometry(ws, x)
    if l_out % out_block:
        raise ValueError(f"out_block {out_block} does not divide L_out {l_out}")
    b = x.shape[0]
    block_b = min(block_b, b)
    (x,) = pad_batch(block_b, x)
    bp = x.shape[0]
    w_t, x_t, w_specs, act_spec, scratch = _group_operands(
        ws, x, l_out, out_block, block_b
    )
    out = pl.pallas_call(
        _make_fwd_kernel(g),
        out_shape=jax.ShapeDtypeStruct((1, l_out, bp, k_final), jnp.float32),
        grid=(l_out // out_block, bp // block_b),
        in_specs=w_specs + [act_spec(2 ** g, k)],
        out_specs=act_spec(1, k_final),
        scratch_shapes=scratch,
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
        name="grouped_log_einsum_exp_fwd",
    )(*w_t, x_t)
    return cell_major(out[0])[:b]


@functools.partial(
    jax.jit, static_argnames=("out_block", "block_b", "interpret")
)
def grouped_log_einsum_exp_bwd_pallas(
    ws: Tuple[jax.Array, ...],
    x: jax.Array,
    g_out: jax.Array,
    out_block: int = 1,
    block_b: int = 128,
    interpret: Optional[bool] = None,
):
    """Fused multi-depth backward: dW for every depth + the input cotangent,
    one kernel launch.

    Args:
      ws / x / out_block / block_b / interpret: as in the forward (residuals
        are the unpadded primals; the caller re-pads).
      g_out: (B, L_out, K_out_final) cotangent of the group output.

    Returns: (gws tuple matching ``ws`` shapes, gx (B, L_out * 2^G, K)).
    """
    interpret = resolve_interpret(interpret)
    g, l_out, k, k_final = _group_geometry(ws, x)
    if l_out % out_block:
        raise ValueError(f"out_block {out_block} does not divide L_out {l_out}")
    b = x.shape[0]
    block_b = min(block_b, b)
    x, g_out = pad_batch(block_b, x, g_out)
    bp = x.shape[0]
    w_t, x_t, w_specs, act_spec, scratch = _group_operands(
        ws, x, l_out, out_block, block_b
    )
    x_spec = act_spec(2 ** g, k)
    # dW blocks index on ti only, so batch tiles (innermost axis) revisit
    # and accumulate into the same block
    outs = pl.pallas_call(
        _make_bwd_kernel(g),
        out_shape=tuple(
            jax.ShapeDtypeStruct(w.shape, jnp.float32) for w in w_t
        ) + (jax.ShapeDtypeStruct(x_t.shape, jnp.float32),),
        grid=(l_out // out_block, bp // block_b),
        in_specs=w_specs + [x_spec, act_spec(1, k_final)],
        out_specs=tuple(w_specs) + (x_spec,),
        scratch_shapes=scratch + scratch,  # activations, then cotangents
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="grouped_log_einsum_exp_bwd",
    )(*w_t, x_t, cell_major(g_out)[None])
    gws = tuple(gw.reshape(w.shape) for gw, w in zip(outs[:g], ws))
    gx = cell_major(outs[g].reshape(l_out * 2 ** g, bp, k))
    return gws, gx[:b]


# ---------------------------------------------------------------------------
# gather-grouped kernels: static-table topology (PD), mixing in-kernel
# ---------------------------------------------------------------------------
def _mix_frame(v_ref, mi, kids, mask_row):
    """``core.layers._log_mix_exp_frame`` for mixing node ``mi`` on its
    statically gathered children: (clamped max, exp'd inputs, stabilized
    sum).  The mask is applied by STATIC selection (padded children become
    NEG_INF rows at trace time -- Pallas kernels cannot capture array
    constants), which selects exactly the values ``jnp.where(mask > 0, ...)``
    selects.

    v_ref: (M, C, K); kids: C einsum rows (B_t, K); mask_row: C static 0/1.
    """
    lnm = [
        kid if m else jnp.full(kid.shape, NEG_INF, kid.dtype)
        for kid, m in zip(kids, mask_row)
    ]
    a = functools.reduce(jnp.maximum, lnm)
    a = jnp.maximum(a, NEG_INF)
    e = [jnp.exp(ln - a) for ln in lnm]
    ssum = functools.reduce(
        jnp.add, [v_ref[mi, ci:ci + 1, :] * ec for ci, ec in enumerate(e)]
    )
    return a, e, ssum


def _gather_fwd_sweep(tables, w_refs, v_refs, rows, ex):
    """The shared forward walk over an in-VMEM row list.

    ``rows`` starts as the segment's input rows; every depth appends its
    einsum rows then its mixing rows (global row order).  Returns
    (rows, e_bases) where e_bases[t] is the list index of depth ``t``'s
    first einsum row, for the backward's residual recompute.
    """
    rows = list(rows)
    e_bases = []
    vi = 0
    for t in range(tables.num_depths):
        left, right = tables.left[t], tables.right[t]
        s = [
            cell_fwd(w_refs[t][li], rows[left[li]], rows[right[li]], ex)
            for li in range(len(left))
        ]
        e_bases.append(len(rows))
        rows.extend(s)
        if tables.mix_child[t] is not None:
            for mi, (child, mask) in enumerate(
                zip(tables.mix_child[t], tables.mix_mask[t])
            ):
                a, _, ssum = _mix_frame(
                    v_refs[vi], mi, [s[c] for c in child], mask
                )
                rows.append(a + jnp.log(ssum))
            vi += 1
    return rows, e_bases


def _make_gather_fwd_kernel(tables):
    d_total = tables.num_depths
    n_mix = tables.num_mix_depths
    r_in = tables.num_in_rows

    def kernel(*refs):
        w_refs = refs[:d_total]
        v_refs = refs[d_total: d_total + n_mix]
        x_ref, o_ref = refs[-2], refs[-1]
        ex = expanders(x_ref.shape[-1])
        rows, _ = _gather_fwd_sweep(
            tables, w_refs, v_refs, [x_ref[r] for r in range(r_in)], ex
        )
        for idx, row in enumerate(rows[r_in:]):
            o_ref[idx] = row.astype(o_ref.dtype)

    return kernel


def _make_gather_bwd_kernel(tables):
    d_total = tables.num_depths
    n_mix = tables.num_mix_depths
    r_in = tables.num_in_rows

    def kernel(*refs):
        w_refs = refs[:d_total]
        v_refs = refs[d_total: d_total + n_mix]
        x_ref = refs[d_total + n_mix]
        g_ref = refs[d_total + n_mix + 1]
        gw_refs = refs[d_total + n_mix + 2: 2 * d_total + n_mix + 2]
        gv_refs = refs[2 * d_total + n_mix + 2: 2 * d_total + 2 * n_mix + 2]
        gx_ref = refs[-1]
        ex = expanders(x_ref.shape[-1])
        # residual-recompute: re-derive every row from the primals
        rows, e_bases = _gather_fwd_sweep(
            tables, w_refs, v_refs, [x_ref[r] for r in range(r_in)], ex
        )
        pl.when(pl.program_id(0) == 0)(
            lambda: _zero(tuple(gw_refs) + tuple(gv_refs))
        )
        zero = jnp.zeros_like(rows[0])
        cot = [zero] * r_in + [
            g_ref[idx] for idx in range(len(rows) - r_in)
        ]
        vi = n_mix
        for t in reversed(range(d_total)):
            e_base = e_bases[t]
            n_e = len(tables.left[t])
            s = rows[e_base: e_base + n_e]
            # mixing backward FIRST: its gradient lands on this depth's
            # einsum rows before their own backward runs
            if tables.mix_child[t] is not None:
                vi -= 1
                v_ref, gv_ref = v_refs[vi], gv_refs[vi]
                m_base = e_base + n_e
                for mi, (child, mask) in enumerate(
                    zip(tables.mix_child[t], tables.mix_mask[t])
                ):
                    _, e, ssum = _mix_frame(
                        v_ref, mi, [s[c] for c in child], mask
                    )
                    ginv = cot[m_base + mi] / jnp.maximum(ssum, S_FLOOR)
                    # static masking (see _mix_frame): masked children
                    # contribute exact zeros to dV and nothing to the scatter
                    for ci, c in enumerate(child):
                        if not mask[ci]:
                            continue
                        ge = ginv * e[ci]
                        gv_ref[mi, ci:ci + 1, :] += jnp.sum(
                            ge, axis=0, keepdims=True
                        )
                        cot[e_base + c] = (
                            cot[e_base + c] + ge * v_ref[mi, ci:ci + 1, :]
                        )
            for li, (lr, rr) in enumerate(
                zip(tables.left[t], tables.right[t])
            ):
                gw, gl, gr = cell_bwd(
                    w_refs[t][li], rows[lr], rows[rr], cot[e_base + li], ex
                )
                gw_refs[t][li] += gw.astype(gw_refs[t].dtype)
                cot[rr] = cot[rr] + gr
                cot[lr] = cot[lr] + gl
        for r in range(r_in):
            gx_ref[r] = cot[r].astype(gx_ref.dtype)

    return kernel


def _gather_geometry(tables, ws, vs, x):
    """Validate the table-carrying shapes; returns (r_new, K)."""
    b, r_in, k = x.shape
    if r_in != tables.num_in_rows:
        raise ValueError(
            f"gather input has {r_in} rows; tables expect "
            f"{tables.num_in_rows}"
        )
    if len(ws) != tables.num_depths:
        raise ValueError(
            f"{len(ws)} weight depths vs {tables.num_depths} table depths"
        )
    for t, w in enumerate(ws):
        l = len(tables.left[t])
        if w.shape != (l, k, k, k):
            raise ValueError(
                f"gather depth {t} weights {w.shape} != {(l, k, k, k)} "
                "(interior depths keep K_out == K)"
            )
    if len(vs) != tables.num_mix_depths:
        raise ValueError(
            f"{len(vs)} mixing depths vs {tables.num_mix_depths} in tables"
        )
    vi = 0
    for t in range((tables.num_depths)):
        if tables.mix_child[t] is None:
            continue
        m, c = len(tables.mix_child[t]), len(tables.mix_child[t][0])
        if vs[vi].shape != (m, c, k):
            raise ValueError(
                f"gather mix depth {t} weights {vs[vi].shape} != {(m, c, k)}"
            )
        vi += 1
    return tables.num_new_rows, k


def _whole(shape):
    """Block spec of an operand every program reads whole (weights)."""
    return pl.BlockSpec(shape, lambda bi: (0,) * len(shape))


def _batch_tile(rows, block_b, k):
    """Block spec of a cell-major (rows, B, K) operand tiled over batch."""
    return pl.BlockSpec((rows, block_b, k), lambda bi: (0, bi, 0))


@functools.partial(
    jax.jit, static_argnames=("tables", "block_b", "interpret")
)
def gather_grouped_log_einsum_exp_pallas(
    tables,
    ws: Tuple[jax.Array, ...],
    vs: Tuple[jax.Array, ...],
    x: jax.Array,
    block_b: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused gather-topology forward: one launch for a table-driven run.

    Args:
      tables: ``core.plan.GatherTables`` (static; baked into the kernel).
      ws: per-depth linear-domain weights, (L_t, K, K, K) each (every depth
        is interior: K_out == K, padded per ``ops.pad_gather_for_lanes``).
      vs: mixing weights for the table's mixing depths, in depth order,
        (M_t, C_t, K) each.
      x: (B, r_in, K) log-domain row buffer below the segment.
      block_b: batch tile (grid is batch-only; the segment is not
        cell-tiled -- see the module docstring).
      interpret: None defers to backend dispatch.

    Returns: (B, r_new, K) float32 -- every new row (einsum rows then mixing
    rows, per depth, in emission order = global row order).
    """
    interpret = resolve_interpret(interpret)
    r_new, k = _gather_geometry(tables, ws, vs, x)
    b = x.shape[0]
    block_b = min(block_b, b)
    (x,) = pad_batch(block_b, x)
    bp = x.shape[0]
    w_t = [flat_weights(w) for w in ws]
    out = pl.pallas_call(
        _make_gather_fwd_kernel(tables),
        out_shape=jax.ShapeDtypeStruct((r_new, bp, k), jnp.float32),
        grid=(bp // block_b,),
        in_specs=[_whole(w.shape) for w in w_t]
        + [_whole(v.shape) for v in vs]
        + [_batch_tile(tables.num_in_rows, block_b, k)],
        out_specs=_batch_tile(r_new, block_b, k),
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
        name="gather_grouped_log_einsum_exp_fwd",
    )(*w_t, *vs, cell_major(x))
    return cell_major(out)[:b]


@functools.partial(
    jax.jit, static_argnames=("tables", "block_b", "interpret")
)
def gather_grouped_log_einsum_exp_bwd_pallas(
    tables,
    ws: Tuple[jax.Array, ...],
    vs: Tuple[jax.Array, ...],
    x: jax.Array,
    g_out: jax.Array,
    block_b: int = 128,
    interpret: Optional[bool] = None,
):
    """Fused gather-topology backward: dW per depth, dV per mixing depth and
    the input-buffer cotangent, one launch (residual-recompute: the forward
    rows and every stabilized frame are re-derived in VMEM from the primals;
    dW/dV accumulate across batch tiles on the sequential batch grid axis).

    Returns: (gws tuple matching ``ws``, gvs tuple matching ``vs``,
    gx (B, r_in, K)).
    """
    interpret = resolve_interpret(interpret)
    r_new, k = _gather_geometry(tables, ws, vs, x)
    b = x.shape[0]
    block_b = min(block_b, b)
    x, g_out = pad_batch(block_b, x, g_out)
    bp = x.shape[0]
    r_in = tables.num_in_rows
    w_t = [flat_weights(w) for w in ws]
    # dW / dV blocks ignore the batch grid index: every batch tile revisits
    # the same block and accumulates
    acc_specs = [_whole(w.shape) for w in w_t] + [_whole(v.shape) for v in vs]
    outs = pl.pallas_call(
        _make_gather_bwd_kernel(tables),
        out_shape=tuple(
            jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in (*w_t, *vs)
        ) + (jax.ShapeDtypeStruct((r_in, bp, k), jnp.float32),),
        grid=(bp // block_b,),
        in_specs=acc_specs
        + [_batch_tile(r_in, block_b, k), _batch_tile(r_new, block_b, k)],
        out_specs=tuple(acc_specs) + (_batch_tile(r_in, block_b, k),),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
        name="gather_grouped_log_einsum_exp_bwd",
    )(*w_t, *vs, cell_major(x), cell_major(g_out))
    d_total = len(ws)
    gws = tuple(gw.reshape(w.shape) for gw, w in zip(outs[:d_total], ws))
    gvs = tuple(outs[d_total: d_total + len(vs)])
    return gws, gvs, cell_major(outs[-1])[:b]
