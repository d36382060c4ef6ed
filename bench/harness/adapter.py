"""Placement of the reference's parameters into the program's pytree.

The benchmark makes the weights itself, keyed by the reference's region
graph (a leaf entry per (leaf region, variable), a tensor per partition, a
vector per mixed region).  :class:`Layout` reads the program's layer tables
once, names every slot of its parameter pytree by the scopes it computes on,
and finds the reference parameter of the same scopes.  A slot whose scopes
the reference does not have is an error: the two structures differ.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np


class StructureMismatch(ValueError):
    pass


def _key(scope) -> Tuple[int, ...]:
    return tuple(sorted(int(v) for v in scope))


class Layout:
    def __init__(self, model, ref):
        graph = ref.g
        self.ref = ref
        region_id = {s: i for i, s in enumerate(graph.regions)}
        part_id = {(graph.regions[l], graph.regions[r]): p
                   for p, (_, l, r) in enumerate(graph.partitions)}
        mixed_id = {r: m for m, r in enumerate(graph.mixed)}
        leaf_index = {r: i for i, r in enumerate(graph.leaves)}
        entry = {(int(graph.entry_leaf[e]), int(graph.entry_var[e])): e
                 for e in range(len(graph.entry_var))}

        ls = model.leaf_spec
        row_scope: Dict[int, Tuple[int, ...]] = {}
        d, r = model.num_vars, ls.num_replica
        self.phi_entry = np.full((d, r), -1, np.int64)
        for j, scope in enumerate(ls.leaf_scopes):
            sc = _key(scope)
            row_scope[j] = sc
            if sc not in region_id or region_id[sc] not in leaf_index:
                raise StructureMismatch(f"program leaf {sc[:4]}... is no reference leaf")
            li = leaf_index[region_id[sc]]
            for v in sc:
                self.phi_entry[v, int(ls.leaf_replica[j])] = entry[(li, v)]
        if (self.phi_entry < 0).any():
            raise StructureMismatch("program has leaf slots the reference does not")

        self.parts: List[np.ndarray] = []
        self.k_out: List[int] = []
        self.mix: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        for spec in model.pair_specs:
            parts = []
            for l in range(spec.num_partitions):
                key = (row_scope[int(spec.left[l])], row_scope[int(spec.right[l])])
                if key not in part_id:
                    raise StructureMismatch(f"program partition over {key[0][:3]}.. | "
                                            f"{key[1][:3]}.. is not in the reference")
                parts.append(part_id[key])
                row_scope[int(spec.einsum_global[l])] = _key(key[0] + key[1])
            self.parts.append(np.asarray(parts, np.int64))
            self.k_out.append(int(spec.k_out))
            if spec.mix_global is None:
                self.mix.append(None)
                continue
            m_n, c_n = spec.mix_child_local.shape
            mix_idx = np.zeros((m_n, c_n), np.int64)
            child_pos = np.zeros((m_n, c_n), np.int64)
            mask = np.asarray(spec.mix_mask) > 0
            for m in range(m_n):
                first = int(spec.mix_child_local[m, 0])
                sc = row_scope[int(spec.einsum_global[first])]
                reg = region_id[sc]
                if reg not in mixed_id:
                    raise StructureMismatch("program mixes a region the reference does not")
                kids = graph.children[reg]
                if int(mask[m].sum()) != len(kids):
                    raise StructureMismatch("mixed region has another number of children")
                for c in range(c_n):
                    if mask[m, c]:
                        p = parts[int(spec.mix_child_local[m, c])]
                        mix_idx[m, c] = mixed_id[reg]
                        child_pos[m, c] = kids.index(p)
                row_scope[int(spec.mix_global[m])] = sc
            self.mix.append((mix_idx, child_pos, mask))

    def to_program(self, ref: dict) -> dict:
        """The program's parameter pytree holding the reference's values
        (jittable: static index tables only)."""
        e = jnp.asarray(self.phi_entry)
        phi = jnp.stack([ref["mu"][e], ref["s"][e]], axis=-1)  # (D, R, K, 2)
        phi = jnp.swapaxes(phi, 1, 2)  # (D, K, R, 2)
        einsum = [self.ref.weights(ref["W"], [int(p) for p in parts]) for parts in self.parts]
        mixing = []
        for k_out, mix in zip(self.k_out, self.mix):
            if mix is None:
                mixing.append(jnp.zeros((0, 0, k_out)))
                continue
            mix_idx, child_pos, mask = mix
            rows = []
            for m in range(mix_idx.shape[0]):
                cols = [ref["V"][int(mix_idx[m, c])][int(child_pos[m, c])] if mask[m, c]
                        else jnp.zeros((k_out,)) for c in range(mix_idx.shape[1])]
                rows.append(jnp.stack(cols))
            mixing.append(jnp.stack(rows))
        return {"phi": phi, "einsum": einsum, "mixing": mixing,
                "class_prior": ref["prior"]}
