"""Einsum Networks: layered, vectorized probabilistic circuits (paper §3).

An ``EiNet`` compiles a region graph into a bottom-up list of (einsum-layer,
mixing-layer) pairs with *static* integer gather tables (built once, on host,
in numpy).  The jitted forward pass is then nothing but:

    leaf rows (EF log-densities summed over each leaf's scope)  ->  for
    each pair: gather(left rows), gather(right rows), one monolithic
    log-einsum-exp, optional mixing logsumexp  ->  append to the row buffer.

This is exactly the paper's design: all product/sum operations of one
topological layer collapse into a single einsum (Eq. 5), products are never
materialized, probabilities stay in the log-domain, weights stay linear.

Also implemented here: exact marginalization (evidence masks), ancestral /
conditional sampling (the induced-tree top-down pass used for Fig. 4
inpainting), and MPE-style argmax decoding.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import plan as plan_lib
from repro.core import region_graph as rg_lib
from repro.dist.sharding import constraint as _cst
from repro.core.exponential_family import ExponentialFamily, Normal
from repro.core.layers import (
    NEG_INF,
    gather_grouped_log_einsum_exp,
    grouped_log_einsum_exp,
    log_einsum_exp,
    log_mix_exp,
    normalize_einsum_weights,
    normalize_mixing_weights,
)
from repro.obs import health as health_lib

# execution planning lives in core.plan; re-exported here for callers (and
# tests) that reach the planner types through the model module
ExecSegment = plan_lib.ExecSegment
VMEM_BUDGET_BYTES = plan_lib.VMEM_BUDGET_BYTES
_GROUP_BLOCK_B = plan_lib._GROUP_BLOCK_B


# query kinds understood by EiNet.query / the serving engine
QUERY_KINDS = (
    "joint_ll",
    "marginal_ll",
    "conditional_ll",
    "sample",
    "conditional_sample",
    "mpe",
)


def _lower_segment(seg) -> None:
    """Record one plan segment's lowering: the walk runs while jit traces,
    so this counts lowerings, not executions.  Device time per segment is
    read from the ``plan.<kind>`` named scope in a profiler trace."""
    obs.METRICS.counter("plan.segment.traces", kind=seg.kind).inc()
    obs.event("plan.segment", kind=seg.kind, start=seg.start, stop=seg.stop)


@dataclasses.dataclass
class PairSpec:
    """Static gather tables for one (product-layer, sum-layer) pair."""

    left: np.ndarray  # (L,) global buffer rows of left children
    right: np.ndarray  # (L,) global buffer rows of right children
    einsum_global: np.ndarray  # (L,) global row id of each simple-sum output
    k_in: int
    k_out: int
    # mixing (None when every sum in this layer has a single child)
    mix_child_local: Optional[np.ndarray]  # (M, C) local partition idx, 0-padded
    mix_mask: Optional[np.ndarray]  # (M, C) 1/0
    mix_global: Optional[np.ndarray]  # (M,) global row ids
    is_final: bool
    # canonical layout (beyond-paper layout optimization, DESIGN.md/§Perf):
    # when the pair's children are exactly the previous layer's outputs, the
    # previous layer is reordered at build time so left = rows [0, L) and
    # right = rows [L, 2L) -- the gather becomes a static slice (zero copy,
    # zero collectives under layer-node sharding).
    canonical: bool = False

    @property
    def num_partitions(self) -> int:
        return len(self.left)

    @property
    def num_mixed(self) -> int:
        return 0 if self.mix_global is None else len(self.mix_global)


@dataclasses.dataclass
class LeafSpec:
    pair_var: np.ndarray  # (P,) variable ids, concatenated leaf scopes
    pair_rep: np.ndarray  # (P,) replica id of the owning leaf
    pair_leaf: np.ndarray  # (P,) owning leaf row (= segment id)
    num_leaves: int
    num_replica: int
    leaf_scopes: List[Tuple[int, ...]]
    leaf_replica: np.ndarray  # (num_leaves,)
    # run layout (``lay_out``): entry s of leaf j's scope sits at [s, j],
    # each scope padded to the longest one ("run")
    run_var: Optional[np.ndarray] = None  # (run, num_leaves) variable ids
    run_rep: Optional[np.ndarray] = None  # (run, num_leaves) replica ids
    run_valid: Optional[np.ndarray] = None  # (run, num_leaves); None: no pad
    # (D * R,) flat run position of each (variable, replica) entry, or
    # run * num_leaves (one past the end) where no leaf holds it
    param_index: Optional[np.ndarray] = None
    pad_share: float = 0.0  # share of run entries that are pad

    def lay_out(self, num_vars: int) -> None:
        """Build the run layout from the (final) leaf order."""
        sizes = np.array([len(s) for s in self.leaf_scopes])
        run = int(sizes.max())
        valid = np.arange(run)[:, None] < sizes[None, :]
        var = np.zeros((run, self.num_leaves), np.int32)
        for j, scope in enumerate(self.leaf_scopes):
            var[: len(scope), j] = scope
        rep = np.repeat(self.leaf_replica.astype(np.int32)[None, :], run, 0)
        index = np.full(num_vars * self.num_replica, valid.size, np.int32)
        index[var[valid] * self.num_replica + rep[valid]] = np.flatnonzero(
            valid)
        self.run_var, self.run_rep, self.param_index = var, rep, index
        self.run_valid = None if valid.all() else valid
        self.pad_share = float(1.0 - valid.mean())
        obs.event("einet.leaf_layout", leaves=self.num_leaves, run=run,
                  pad_share=self.pad_share)


class EiNet:
    """A compiled Einsum Network over a region graph.

    Static structure lives on the instance; learnable state is a pytree
    ``params`` produced by :meth:`init` and consumed by the pure functions
    :meth:`log_likelihood`, :meth:`forward`, :meth:`sample`, ... so the model
    composes with jit / grad / pjit.
    """

    # the query surface the serving engine compiles programs for (the
    # mixture model declares its own mixture_* kinds the same way)
    query_kinds = QUERY_KINDS

    def __init__(
        self,
        graph: rg_lib.RegionGraph,
        num_sums: int = 10,
        num_classes: int = 1,
        exponential_family: Optional[ExponentialFamily] = None,
        impl: str = "xla",
        grouped: bool = True,
        vmem_budget: Optional[int] = None,
        verify: Optional[str] = None,
        health: Optional[bool] = None,
    ):
        self.graph = graph
        self.K = int(num_sums)
        self.num_classes = int(num_classes)
        self.ef = exponential_family or Normal()
        self.num_vars = graph.num_vars
        self.impl = impl
        self.grouped = bool(grouped)
        self.vmem_budget = plan_lib.resolve_vmem_budget(vmem_budget)
        self._build()
        self.plan = plan_lib.plan_circuit(
            self.pair_specs, grouped=self.grouped,
            vmem_budget=self.vmem_budget,
        )
        self.exec_plan = self.plan.segments
        # numerical-health telemetry (repro.obs.health): ctor knob wins, else
        # the REPRO_HEALTH env var; the spec is fixed by the execution plan
        self.health = health_lib.resolve_health(health)
        self.health_spec = health_lib.spec_for(self)
        # static verification (repro.analysis.verify): the ctor knob wins,
        # else the REPRO_VERIFY env var ("off" | "report" | "raise")
        self.verify_report = None
        mode = verify if verify is not None else os.environ.get(
            "REPRO_VERIFY", "off").strip().lower()
        if mode in ("off", "", "0"):
            return
        if mode not in ("report", "raise"):
            raise ValueError(
                f"verify={mode!r}; expected 'off', 'report' or 'raise'")
        from repro.analysis.verify import VerifyError, verify_einet

        self.verify_report = verify_einet(self)
        if not self.verify_report.ok:
            if mode == "raise":
                raise VerifyError(self.verify_report)
            print(self.verify_report.format_report())

    # ------------------------------------------------------------------ build
    def _build(self) -> None:
        graph = self.graph
        leaves, pairs = rg_lib.topological_layers(graph)
        leaf_scopes = [graph.regions[i] for i in leaves]
        leaf_replica, num_replica = rg_lib.assign_replicas(leaf_scopes)

        pair_var = np.concatenate(
            [np.asarray(s, dtype=np.int32) for s in leaf_scopes]
        )
        pair_rep = np.concatenate(
            [
                np.full(len(s), leaf_replica[i], dtype=np.int32)
                for i, s in enumerate(leaf_scopes)
            ]
        )
        pair_leaf = np.concatenate(
            [np.full(len(s), i, dtype=np.int32) for i, s in enumerate(leaf_scopes)]
        )
        self.leaf_spec = LeafSpec(
            pair_var=pair_var,
            pair_rep=pair_rep,
            pair_leaf=pair_leaf,
            num_leaves=len(leaves),
            num_replica=int(num_replica),
            leaf_scopes=leaf_scopes,
            leaf_replica=leaf_replica,
        )

        region_row: Dict[int, int] = {r: i for i, r in enumerate(leaves)}
        next_row = len(leaves)
        self.pair_specs: List[PairSpec] = []
        for t, (l_p, l_s) in enumerate(pairs):
            is_final = t == len(pairs) - 1
            if is_final:
                assert l_s == [graph.root], "final sum layer must be the root"
            k_out = self.num_classes if is_final else self.K
            part_local = {p: i for i, p in enumerate(l_p)}
            left = np.array(
                [region_row[graph.partitions[p][1]] for p in l_p], dtype=np.int32
            )
            right = np.array(
                [region_row[graph.partitions[p][2]] for p in l_p], dtype=np.int32
            )
            einsum_global = np.arange(next_row, next_row + len(l_p), dtype=np.int32)
            next_row += len(l_p)

            mixed_regions = [s for s in l_s if len(graph.region_children[s]) > 1]
            mix_child_local = mix_mask = mix_global = None
            if mixed_regions:
                c_max = max(len(graph.region_children[s]) for s in mixed_regions)
                mix_child_local = np.zeros((len(mixed_regions), c_max), np.int32)
                mix_mask = np.zeros((len(mixed_regions), c_max), np.float32)
                for m, s in enumerate(mixed_regions):
                    kids = [part_local[p] for p in graph.region_children[s]]
                    mix_child_local[m, : len(kids)] = kids
                    mix_mask[m, : len(kids)] = 1.0
                mix_global = np.arange(
                    next_row, next_row + len(mixed_regions), dtype=np.int32
                )
                next_row += len(mixed_regions)
                for m, s in enumerate(mixed_regions):
                    region_row[s] = int(mix_global[m])
            for s in l_s:
                if len(graph.region_children[s]) == 1:
                    p = graph.region_children[s][0]
                    region_row[s] = int(einsum_global[part_local[p]])

            self.pair_specs.append(
                PairSpec(
                    left=left,
                    right=right,
                    einsum_global=einsum_global,
                    k_in=self.K,
                    k_out=k_out,
                    mix_child_local=mix_child_local,
                    mix_mask=mix_mask,
                    mix_global=mix_global,
                    is_final=is_final,
                )
            )
        self.total_rows = next_row  # includes final-layer rows (never buffered)
        self.root_row = region_row[graph.root]
        # rows that live in the value buffer (everything below the final pair)
        final = self.pair_specs[-1]
        self.buffer_rows = final.einsum_global[0]
        self._canonicalize()
        self.leaf_spec.lay_out(self.num_vars)
        self.needs_buffer = any(not p.canonical for p in self.pair_specs)

    def _canonicalize(self) -> None:
        """Beyond-paper layout optimization: reorder each layer so children
        are contiguous -- the paper's §3.3 'extracting and concatenating
        slices ... bookkeeping overhead' becomes two static slices, which
        also shard with zero collectives (left/right halves of the L-sharded
        output below).  Applies whenever a pair's children are exactly the
        previous layer's outputs, each consumed once (true for every pair of
        the RAT structure); other pairs keep the general gather path."""
        specs = self.pair_specs
        for i in range(len(specs) - 1, -1, -1):
            cur = specs[i]
            child = np.concatenate([cur.left, cur.right])
            if i == 0:
                n = self.leaf_spec.num_leaves
                if len(child) != n or sorted(child.tolist()) != list(range(n)):
                    continue
                # reorder the leaf layer itself
                order = child.tolist()
                ls = self.leaf_spec
                scopes = [ls.leaf_scopes[j] for j in order]
                replica = ls.leaf_replica[order]
                ls.leaf_scopes = scopes
                ls.leaf_replica = replica
                ls.pair_var = np.concatenate(
                    [np.asarray(s, np.int32) for s in scopes])
                ls.pair_rep = np.concatenate([
                    np.full(len(s), replica[j], np.int32)
                    for j, s in enumerate(scopes)])
                ls.pair_leaf = np.concatenate([
                    np.full(len(s), j, np.int32)
                    for j, s in enumerate(scopes)])
                half = len(cur.left)
                cur.left = np.arange(half, dtype=np.int32)
                cur.right = np.arange(half, 2 * half, dtype=np.int32)
                cur.canonical = True
                continue
            prev = specs[i - 1]
            if prev.mix_global is not None:
                continue
            base = int(prev.einsum_global[0])
            rows = prev.einsum_global.tolist()
            if sorted(child.tolist()) != rows:
                continue
            order = [int(r) - base for r in child]  # new local -> old local
            prev.left = prev.left[order]
            prev.right = prev.right[order]
            half = len(cur.left)
            cur.left = prev.einsum_global[:half]
            cur.right = prev.einsum_global[half:]
            cur.canonical = True

    # ------------------------------------------------------------------- plan
    # (the planner itself lives in core.plan: ``plan_circuit`` compiles the
    # pair list into ``self.plan`` at construction time)
    @property
    def grouped_active(self) -> bool:
        """True when the forward/backward hot path runs fused segments."""
        return self.plan.grouped_active

    def grouping_summary(self) -> Dict[str, Any]:
        """Kernel-launch accounting for one forward pass: the per-layer
        schedule vs the grouped plan (benchmarks record this as the
        ``grouping`` field next to wall-clock)."""
        return self.plan.summary()

    # ------------------------------------------------------------- parameters
    def init(self, key: jax.Array) -> Dict[str, Any]:
        keys = jax.random.split(key, len(self.pair_specs) + 2)
        phi = self.ef.init_phi(
            keys[0], (self.num_vars, self.K, self.leaf_spec.num_replica)
        )
        einsum_w = []
        mixing_v = []
        for i, spec in enumerate(self.pair_specs):
            w = jax.random.uniform(
                keys[i + 1],
                (spec.num_partitions, spec.k_out, spec.k_in, spec.k_in),
                minval=0.1,
                maxval=1.0,
            )
            einsum_w.append(normalize_einsum_weights(w))
            if spec.mix_global is not None:
                kv = jax.random.fold_in(keys[i + 1], 1)
                v = jax.random.uniform(
                    kv,
                    (spec.num_mixed, spec.mix_child_local.shape[1], spec.k_out),
                    minval=0.1,
                    maxval=1.0,
                )
                mixing_v.append(
                    normalize_mixing_weights(v, jnp.asarray(spec.mix_mask))
                )
            else:
                mixing_v.append(jnp.zeros((0, 0, spec.k_out)))
        # strong float32: a weak-typed prior changes aval after the first EM
        # update and forces a silent recompile of every jitted training step
        class_prior = jnp.full(
            (self.num_classes,), 1.0 / self.num_classes, dtype=jnp.float32
        )
        return {
            "phi": phi,
            "einsum": einsum_w,
            "mixing": mixing_v,
            "class_prior": class_prior,
        }

    def num_params(self, params: Dict[str, Any]) -> int:
        return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))

    # ---------------------------------------------------------------- forward
    def run_columns(self, a: jax.Array) -> jax.Array:
        """(B, D) -> (run, num_leaves, B): ``a``'s columns in the run layout
        (``LeafSpec.lay_out``), the batch minor."""
        return _cst(a.T[self.leaf_spec.run_var], (None, "einet_nodes", "batch"))

    def leaf_rows(
        self,
        params: Dict[str, Any],
        x: jax.Array,
        marg_mask: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Leaf-region rows (B, num_leaves, K): each leaf's log-density, the
        sum of its scope's per-variable EF log-densities, with marginalized
        variables (``marg_mask`` False) counting log 1 = 0.

        Computed in the run layout with the batch minor, on the 128 lanes of
        a TPU vreg (K or |T| there would leave most of them idle): x's
        columns and phi's entries are taken in run order once, and each
        leaf's entries are added into a (num_leaves, K, B) accumulator, so
        no (B, D, K, R) or (B, P, K) tensor is written; only the result is
        transposed.  The entries are added one after another in scope
        order, as a segment-sum adds them: a row sums hundreds of terms at
        |row| ~ 1e3, where a tree-ordered sum rounds up to 1e-3 apart, and
        that moves the EM statistics by ~1e-5 against a float32 reference
        summed in scope order.  theta and A(theta) are computed before the
        loop, so the batch-wide arithmetic is the same whatever the batch
        size.
        """
        ls = self.leaf_spec
        run, leaves = ls.run_var.shape
        theta = self.ef.expectation_to_natural(
            params["phi"][ls.run_var, :, ls.run_rep])  # (run, leaves, K, T)
        a = self.ef.log_normalizer(theta)  # (run, leaves, K)
        xs = self.run_columns(x)  # (run, leaves, B)
        keep = None if marg_mask is None else self.run_columns(marg_mask)
        if ls.run_valid is not None:
            valid = jnp.asarray(ls.run_valid)[:, :, None]
            keep = valid if keep is None else keep & valid

        def add_entry(s, acc):
            e = self.ef.log_density(xs[s][:, None, :], theta[s][:, :, None, :],
                                    a[s][:, :, None])  # (leaves, K, B)
            if keep is not None:
                e = jnp.where(keep[s][:, None, :], e, 0.0)
            return acc + e

        # eight entries per trip keep the loop's own cost small against them
        rows = jax.lax.fori_loop(
            0, run, add_entry,
            jnp.zeros((leaves, self.K, x.shape[0]), theta.dtype),
            unroll=min(run, 8))
        return jnp.transpose(rows, (2, 0, 1))

    def forward_from_leaves(
        self,
        einsum_w: List[jax.Array],
        mixing_v: List[jax.Array],
        leaf_rows: jax.Array,
        return_cache: bool = False,
    ):
        """Bottom-up pass from the leaf rows (``leaf_rows``).  Returns
        (B, num_classes) root log-densities (and the per-pair cache when
        ``return_cache``).

        Canonical pairs read their children as two static slices of the layer
        below (zero-gather fast path); the global row buffer is materialized
        only for non-canonical pairs or when the sampling cache is requested.

        When the execution plan has fused segments (``grouped_active``) and
        no cache is requested, the pass walks the plan instead of the pair
        list: each fused segment is one grouped log-einsum-exp (a single
        kernel launch under ``impl="pallas"``), per-layer segments keep the
        existing op.  The sampling path (``return_cache``) needs every
        depth's activations by definition, so it always runs per-layer.
        """
        leaf_out = _cst(leaf_rows, ("batch", "einet_nodes", None))
        if self.grouped_active and not return_cache:
            return self._forward_planned(einsum_w, mixing_v, leaf_out)
        buffer = leaf_out
        build_buffer = self.needs_buffer or return_cache
        cache = {"S": []}
        prev_out = leaf_out
        root_out = None
        for i, spec in enumerate(self.pair_specs):
            if spec.canonical:
                half = spec.num_partitions
                n_l = prev_out[:, :half, :]
                n_r = prev_out[:, half: 2 * half, :]
            else:
                n_l = buffer[:, spec.left, :]
                n_r = buffer[:, spec.right, :]
            s = log_einsum_exp(einsum_w[i], n_l, n_r, impl=self.impl)  # (B,L,k)
            s = _cst(s, ("batch", "einet_nodes", None))
            health_lib.tap_segment(s)
            new_rows = [s]
            mix_out = None
            if spec.mix_global is not None:
                ln = s[:, spec.mix_child_local, :]  # (B, M, C, k_out)
                mix_out = log_mix_exp(mixing_v[i], ln, jnp.asarray(spec.mix_mask))
                new_rows.append(mix_out)
            if return_cache:
                cache["S"].append(s)
            if spec.is_final:
                root_out = mix_out if spec.mix_global is not None else s[:, 0, :]
            else:
                prev_out = s if mix_out is None else jnp.concatenate(
                    [s, mix_out], axis=1)
                if build_buffer:
                    buffer = jnp.concatenate([buffer] + new_rows, axis=1)
        if root_out.ndim == 3:  # root was a mixing row: (B, 1, num_classes)
            root_out = root_out[:, 0, :]
        if return_cache:
            cache["buffer"] = buffer
            return root_out, cache
        return root_out

    def _forward_planned(
        self,
        einsum_w: List[jax.Array],
        mixing_v: List[jax.Array],
        leaf_out: jax.Array,
    ) -> jax.Array:
        """The depth-grouped bottom-up pass (``self.plan`` walk).

        All-canonical structures (``needs_buffer`` is False, the RAT family)
        walk "fused"/"layer" segments over the previous layer's outputs --
        no row buffer exists, every pair reads two static slices, and fused
        segments are exactly the canonical chains the grouped kernel
        implements.  Structures with gather topology (PD) walk
        "gather"/"layer" segments over the materialized global row buffer:
        a gather segment is one table-driven kernel covering a run of
        depths (mixing in-kernel), a layer segment is the per-pair op on
        buffer-gathered children.  Either way every segment computes the
        identical per-pair math in the identical order, making this path
        bit-exact against the per-layer loop under ``impl="xla"`` by
        construction.
        """
        if self.needs_buffer:
            return self._forward_planned_buffer(einsum_w, mixing_v, leaf_out)
        prev_out = leaf_out
        root_out = None
        for seg in self.exec_plan:
            last = self.pair_specs[seg.stop - 1]
            _lower_segment(seg)
            with jax.named_scope(f"plan.{seg.kind}"):
                if seg.fused:
                    ws = [einsum_w[t] for t in range(seg.start, seg.stop)]
                    s = grouped_log_einsum_exp(
                        ws, prev_out, seg.out_block, seg.block_b,
                        impl=self.impl
                    )
                else:
                    half = last.num_partitions
                    s = log_einsum_exp(
                        einsum_w[seg.start],
                        prev_out[:, :half, :],
                        prev_out[:, half: 2 * half, :],
                        impl=self.impl,
                    )
                s = _cst(s, ("batch", "einet_nodes", None))
                health_lib.tap_segment(s)
                mix_out = None
                if last.mix_global is not None:
                    ln = s[:, last.mix_child_local, :]
                    mix_out = log_mix_exp(
                        mixing_v[seg.stop - 1], ln, jnp.asarray(last.mix_mask)
                    )
                if last.is_final:
                    root_out = (
                        mix_out if last.mix_global is not None else s[:, 0, :]
                    )
                else:
                    prev_out = s if mix_out is None else jnp.concatenate(
                        [s, mix_out], axis=1)
        if root_out.ndim == 3:
            root_out = root_out[:, 0, :]
        return root_out

    def _forward_planned_buffer(
        self,
        einsum_w: List[jax.Array],
        mixing_v: List[jax.Array],
        leaf_out: jax.Array,
    ) -> jax.Array:
        """Row-buffer plan walk for gather-topology structures.

        The buffer is indexed by GLOBAL row id (leaves first, then each
        pair's einsum rows followed by its mixing rows -- the allocation
        order of ``_build``), so a gather segment's output rows append in
        exactly global order and layer segments read ``spec.left`` /
        ``spec.right`` directly.  The planner never emits "fused"
        (slice-tiled) segments here: they skip materializing interior rows,
        which would leave holes in the buffer.
        """
        buffer = leaf_out
        root_out = None
        for seg in self.exec_plan:
            _lower_segment(seg)
            if seg.kind == "gather":
                with jax.named_scope("plan.gather"):
                    ws = tuple(
                        einsum_w[t] for t in range(seg.start, seg.stop)
                    )
                    vs = tuple(
                        mixing_v[t]
                        for t in range(seg.start, seg.stop)
                        if self.pair_specs[t].mix_global is not None
                    )
                    w0 = buffer.shape[1]
                    buffer = gather_grouped_log_einsum_exp(
                        seg.tables, ws, vs, buffer,
                        block_b=seg.block_b, impl=self.impl,
                    )
                    buffer = _cst(buffer, ("batch", "einet_nodes", None))
                    health_lib.tap_segment(buffer[:, w0:, :])
                continue
            with jax.named_scope(f"plan.{seg.kind}"):
                spec = self.pair_specs[seg.start]
                n_l = buffer[:, spec.left, :]
                n_r = buffer[:, spec.right, :]
                s = log_einsum_exp(
                    einsum_w[seg.start], n_l, n_r, impl=self.impl
                )
                s = _cst(s, ("batch", "einet_nodes", None))
                health_lib.tap_segment(s)
                mix_out = None
                if spec.mix_global is not None:
                    ln = s[:, spec.mix_child_local, :]
                    mix_out = log_mix_exp(
                        mixing_v[seg.start], ln, jnp.asarray(spec.mix_mask)
                    )
                if spec.is_final:
                    root_out = (
                        mix_out if spec.mix_global is not None else s[:, 0, :]
                    )
                else:
                    new = s if mix_out is None else jnp.concatenate(
                        [s, mix_out], axis=1)
                    buffer = jnp.concatenate([buffer, new], axis=1)
        if root_out.ndim == 3:
            root_out = root_out[:, 0, :]
        return root_out

    def forward(
        self,
        params: Dict[str, Any],
        x: jax.Array,
        marg_mask: Optional[jax.Array] = None,
        return_cache: bool = False,
    ):
        return self.forward_from_leaves(
            params["einsum"], params["mixing"],
            self.leaf_rows(params, x, marg_mask), return_cache=return_cache,
        )

    def log_likelihood(
        self,
        params: Dict[str, Any],
        x: jax.Array,
        marg_mask: Optional[jax.Array] = None,
    ) -> jax.Array:
        """log P(x) = logsumexp_c [log prior_c + log P(x | c)], shape (B,)."""
        root = self.forward(params, x, marg_mask)
        return jax.scipy.special.logsumexp(
            root + jnp.log(params["class_prior"])[None, :], axis=-1
        )

    def conditional_log_likelihood(
        self,
        params: Dict[str, Any],
        x: jax.Array,
        query_mask: jax.Array,
        evidence_mask: jax.Array,
    ) -> jax.Array:
        """log p(x_q | x_e) = log p(x_q, x_e) - log p(x_e)  (Eq. 1, exact)."""
        joint = self.log_likelihood(params, x, query_mask | evidence_mask)
        ev = self.log_likelihood(params, x, evidence_mask)
        return joint - ev

    # --------------------------------------------------------------- sampling
    def sample(
        self,
        params: Dict[str, Any],
        key: jax.Array,
        num_samples: int,
        mode: str = "sample",
    ) -> jax.Array:
        """Unconditional ancestral sampling: (num_samples, D)."""
        x = jnp.zeros((num_samples, self.num_vars))
        marg = jnp.zeros((num_samples, self.num_vars), dtype=bool)
        return self.conditional_sample(params, key, x, marg, mode=mode)

    def conditional_sample(
        self,
        params: Dict[str, Any],
        key: jax.Array,
        x: jax.Array,
        evidence_mask: jax.Array,
        mode: str = "sample",
    ) -> jax.Array:
        """Sample X_m ~ p(. | x_e): the Fig. 4 inpainting operation.

        Bottom-up pass with the evidence marginalized out of the complement,
        then a top-down induced-tree pass where every categorical choice is
        re-weighted by the children's (evidence-conditioned) log-likelihoods.
        ``mode='argmax'`` gives a greedy MPE-style decoding instead.
        """
        b = x.shape[0]
        root, cache = self.forward(params, x, evidence_mask, return_cache=True)
        buffer = cache["buffer"]
        dummy = self.total_rows
        comp = jnp.full((b, self.total_rows + 1), -1, dtype=jnp.int32)
        # root class choice
        logits = root + jnp.log(params["class_prior"])[None, :]
        if mode == "argmax":
            c0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            c0 = jax.random.categorical(sub, logits, axis=-1).astype(jnp.int32)
        comp = comp.at[:, self.root_row].set(c0)
        rows_b = jnp.arange(b)[:, None]

        for i in reversed(range(len(self.pair_specs))):
            spec = self.pair_specs[i]
            s_cache = cache["S"][i]  # (B, L, k_out)
            # -- mixing rows first: they activate einsum rows
            if spec.mix_global is not None:
                k = comp[:, spec.mix_global]  # (B, M)
                active = k >= 0
                kk = jnp.maximum(k, 0)
                v = params["mixing"][i]  # (M, C, k_out)
                logv = jnp.log(jnp.maximum(v, 1e-38))  # (M, C, k_out)
                lv = jnp.take_along_axis(
                    logv[None].repeat(b, 0), kk[:, :, None, None], axis=3
                )[..., 0]  # (B, M, C)
                child_ll = s_cache[:, spec.mix_child_local, :]  # (B, M, C, k_out)
                cll = jnp.take_along_axis(child_ll, kk[:, :, None, None], axis=3)[
                    ..., 0
                ]  # (B, M, C)
                logits = jnp.where(
                    jnp.asarray(spec.mix_mask)[None] > 0, lv + cll, NEG_INF
                )
                if mode == "argmax":
                    cidx = jnp.argmax(logits, axis=-1)
                else:
                    key, sub = jax.random.split(key)
                    cidx = jax.random.categorical(sub, logits, axis=-1)
                child_local = jnp.take_along_axis(
                    jnp.asarray(spec.mix_child_local)[None].repeat(b, 0),
                    cidx[:, :, None],
                    axis=2,
                )[..., 0]  # (B, M)
                child_global = jnp.asarray(spec.einsum_global)[child_local]
                rows = jnp.where(active, child_global, dummy)
                comp = comp.at[rows_b, rows].set(kk)
            # -- einsum rows: choose (i, j) and activate the two children
            k = comp[:, spec.einsum_global]  # (B, L)
            active = k >= 0
            kk = jnp.maximum(k, 0)
            w = params["einsum"][i]  # (L, k_out, K, K)
            wk = w[jnp.arange(spec.num_partitions)[None], kk]  # (B, L, K, K)
            n_l = buffer[:, spec.left, :]  # (B, L, K)
            n_r = buffer[:, spec.right, :]
            logits = (
                jnp.log(jnp.maximum(wk, 1e-38))
                + n_l[:, :, :, None]
                + n_r[:, :, None, :]
            ).reshape(b, spec.num_partitions, -1)
            if mode == "argmax":
                flat = jnp.argmax(logits, axis=-1)
            else:
                key, sub = jax.random.split(key)
                flat = jax.random.categorical(sub, logits, axis=-1)
            ii = (flat // self.K).astype(jnp.int32)
            jj = (flat % self.K).astype(jnp.int32)
            lrows = jnp.where(active, jnp.asarray(spec.left)[None], dummy)
            rrows = jnp.where(active, jnp.asarray(spec.right)[None], dummy)
            comp = comp.at[rows_b, lrows].set(ii)
            comp = comp.at[rows_b, rrows].set(jj)

        # -- leaves: sample every variable of every active leaf
        ls = self.leaf_spec
        k_leaf = comp[:, : ls.num_leaves]  # (B, num_leaves)
        k_p = k_leaf[:, ls.pair_leaf]  # (B, P)
        act_p = k_p >= 0
        kk = jnp.maximum(k_p, 0)
        phi = params["phi"][ls.pair_var, :, ls.pair_rep]  # (P, K, T)
        phi_sel = jnp.take_along_axis(
            phi[None].repeat(b, 0), kk[:, :, None, None], axis=2
        )[:, :, 0, :]  # (B, P, T)
        key, sub = jax.random.split(key)
        if mode == "argmax":
            draws = self.ef.mode(phi_sel)  # deterministic MPE-style decode
        else:
            draws = self.ef.sample(sub, phi_sel)  # (B, P)
        cols = jnp.where(act_p, jnp.asarray(ls.pair_var)[None], self.num_vars)
        out = jnp.zeros((b, self.num_vars + 1))
        out = out.at[rows_b, cols].set(draws)[:, : self.num_vars]
        return jnp.where(evidence_mask, x, out)

    def conditional_sample_per_key(
        self,
        params: Dict[str, Any],
        keys: jax.Array,
        x: jax.Array,
        evidence_mask: jax.Array,
        mode: str = "sample",
    ) -> jax.Array:
        """Row-independent conditional sampling: one PRNG key per batch row.

        vmap over the batch makes every row's draw a pure function of its own
        (key, x, evidence) triple -- results are invariant to how requests
        are coalesced into micro-batches, which is what lets the serving
        engine pad buckets with filler rows without perturbing real rows.
        """

        def one(k, xi, ei):
            return self.conditional_sample(
                params, k, xi[None], ei[None], mode=mode
            )[0]

        return jax.vmap(one)(keys, x, evidence_mask)

    # ----------------------------------------------------------------- query
    def query(self, params: Dict[str, Any], batch: Dict[str, Any],
              kind: str) -> jax.Array:
        """Uniform exact-inference entry point (the serving-engine surface).

        ``batch`` carries "x" (B, D) float32, "evidence_mask" / "query_mask"
        (B, D) bool, and "keys" (B, 2) uint32 per-row PRNG keys; each kind
        ignores the fields it does not need, so one input signature covers
        every program in the serving cache.

        Kinds: "joint_ll" -> (B,) log p(x); "marginal_ll" -> (B,) log p(x_e);
        "conditional_ll" -> (B,) log p(x_q | x_e); "sample" -> (B, D)
        unconditional draws; "conditional_sample" -> (B, D) draws of the
        evidence complement; "mpe" -> (B, D) greedy argmax decode.
        """
        x = batch["x"]
        if kind == "joint_ll":
            return self.log_likelihood(params, x)
        if kind == "marginal_ll":
            return self.log_likelihood(params, x, batch["evidence_mask"])
        if kind == "conditional_ll":
            return self.conditional_log_likelihood(
                params, x, batch["query_mask"], batch["evidence_mask"]
            )
        if kind == "sample":
            return self.conditional_sample_per_key(
                params, batch["keys"], jnp.zeros_like(x),
                jnp.zeros_like(batch["evidence_mask"]),
            )
        if kind == "conditional_sample":
            return self.conditional_sample_per_key(
                params, batch["keys"], x, batch["evidence_mask"]
            )
        if kind == "mpe":
            return self.conditional_sample_per_key(
                params, batch["keys"], x, batch["evidence_mask"], mode="argmax"
            )
        raise ValueError(f"unknown query kind {kind!r}; one of {QUERY_KINDS}")

    # ------------------------------------------------------------- projection
    def project_params(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Re-normalize all weights + clamp EF parameters to valid domains."""
        out = dict(params)
        out["phi"] = self.ef.project_phi(params["phi"])
        out["einsum"] = [normalize_einsum_weights(w) for w in params["einsum"]]
        out["mixing"] = [
            normalize_mixing_weights(v, jnp.asarray(spec.mix_mask))
            if spec.mix_global is not None
            else v
            for v, spec in zip(params["mixing"], self.pair_specs)
        ]
        out["class_prior"] = jnp.maximum(params["class_prior"], 1e-12)
        out["class_prior"] = out["class_prior"] / jnp.sum(out["class_prior"])
        return out
