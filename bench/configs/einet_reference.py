"""Plain float32 reference of an Einsum Network (Peharz et al., ICML 2020).

It builds its own region graph (Poon-Domingos or RAT random binary trees),
walks it region by region and partition by partition, and computes:

* per-row log-likelihoods, with marginalised variables (Gaussian leaves);
* the EM statistics of a batch, by autodiff of the summed log-likelihood
  (paper Eq. 6), the M-step and the stochastic-EM blend (Eqs. 8/9);
* the posterior over each leaf region's components (for sample moments);
* the greedy top-down MPE decode.

It imports nothing of the system under test.  Its parameters are keyed by
the graph's own objects: a leaf entry per (leaf region, variable), a weight
tensor per partition, a mixing vector per region with several partitions.
Contractions run at highest precision; callers run it on the host CPU.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Scope = Tuple[int, ...]
NEG = -1e30


@dataclasses.dataclass
class Graph:
    num_vars: int
    regions: List[Scope]
    partitions: List[Tuple[int, int, int]]  # (parent, left, right) region ids
    root: int

    def __post_init__(self):
        self.children: Dict[int, List[int]] = {i: [] for i in range(len(self.regions))}
        for p, (parent, _, _) in enumerate(self.partitions):
            self.children[parent].append(p)
        self.leaves = [r for r in range(len(self.regions)) if not self.children[r]]
        self.mixed = [r for r in range(len(self.regions)) if len(self.children[r]) > 1]
        # partitions by level: a level holds every partition whose two child
        # regions are complete (leaves, or all their partitions done)
        done_regions = set(self.leaves)
        done_parts: set = set()
        levels: List[List[int]] = []
        pending = list(range(len(self.partitions)))
        while pending:
            ready = [p for p in pending if self.partitions[p][1] in done_regions
                     and self.partitions[p][2] in done_regions]
            if not ready:
                raise ValueError("region graph has a cycle or a dangling region")
            levels.append(ready)
            done_parts.update(ready)
            pending = [p for p in pending if p not in done_parts]
            for p in ready:
                parent = self.partitions[p][0]
                if all(q in done_parts for q in self.children[parent]):
                    done_regions.add(parent)
        self.levels = levels
        # leaf entries: (leaf index, variable) in leaf order, scope order
        self.entry_leaf = np.concatenate(
            [np.full(len(self.regions[r]), i, np.int32) for i, r in enumerate(self.leaves)])
        self.entry_var = np.concatenate(
            [np.asarray(self.regions[r], np.int32) for r in self.leaves])


class _Builder:
    def __init__(self, num_vars):
        self.num_vars = num_vars
        self.ids: Dict[Scope, int] = {}
        self.regions: List[Scope] = []
        self.partitions: List[Tuple[int, int, int]] = []

    def region(self, scope) -> int:
        scope = tuple(sorted(scope))
        if scope not in self.ids:
            self.ids[scope] = len(self.regions)
            self.regions.append(scope)
        return self.ids[scope]

    def partition(self, parent, left, right):
        if (parent, left, right) in self.partitions or (parent, right, left) in self.partitions:
            return
        self.partitions.append((parent, left, right))

    def build(self) -> Graph:
        root = self.region(range(self.num_vars))
        for parent, left, right in self.partitions:
            a, b = set(self.regions[left]), set(self.regions[right])
            if a & b or a | b != set(self.regions[parent]):
                raise ValueError("partition is not a decomposition of its parent")
        return Graph(self.num_vars, self.regions, self.partitions, root)


def poon_domingos(height: int, width: int, delta: int, channels: int,
                  axes: Sequence[str]) -> Graph:
    """Poon-Domingos rectangles cut at absolute multiples of ``delta``;
    variable id = (row * width + col) * channels + channel."""
    b = _Builder(height * width * channels)

    def scope(r0, r1, c0, c1):
        return [(r * width + c) * channels + ch
                for r in range(r0, r1) for c in range(c0, c1) for ch in range(channels)]

    def cuts(lo, hi):
        return [p for p in range(delta * (lo // delta + 1), hi, delta) if lo < p < hi]

    seen = set()
    stack = [(0, height, 0, width)]
    while stack:
        rect = stack.pop()
        if rect in seen:
            continue
        seen.add(rect)
        r0, r1, c0, c1 = rect
        rid = b.region(scope(*rect))
        splits = []
        if "h" in axes:
            splits += [((r0, p, c0, c1), (p, r1, c0, c1)) for p in cuts(r0, r1)]
        if "w" in axes:
            splits += [((r0, r1, c0, p), (r0, r1, p, c1)) for p in cuts(c0, c1)]
        for one, two in splits:
            b.partition(rid, b.region(scope(*one)), b.region(scope(*two)))
            stack += [one, two]
    return b.build()


def random_binary_trees(num_vars: int, depth: int, repetitions: int,
                        structure_seed: int = 0) -> Graph:
    """RAT-SPN structure: ``repetitions`` random balanced binary splits of
    the variables down to ``depth``, each split a random permutation drawn
    from ``RandomState(structure_seed)`` in depth-first, left-first order."""
    rng = np.random.RandomState(structure_seed)
    b = _Builder(num_vars)
    root = b.region(range(num_vars))

    def split(rid, scope, d):
        if d == 0 or len(scope) <= 1:
            return
        perm = rng.permutation(len(scope))
        half = len(scope) // 2
        left = tuple(sorted(scope[i] for i in perm[:half]))
        right = tuple(sorted(scope[i] for i in perm[half:]))
        lid, rid2 = b.region(left), b.region(right)
        b.partition(rid, lid, rid2)
        split(lid, left, d - 1)
        split(rid2, right, d - 1)

    for _ in range(repetitions):
        split(root, tuple(range(num_vars)), depth)
    return b.build()


def graph_for(cfg: dict) -> Graph:
    if cfg["structure"] == "pd":
        return poon_domingos(cfg["height"], cfg["width"], cfg["delta"],
                             cfg["num_channels"], cfg["pd_axes"])
    if cfg["structure"] == "rat":
        return random_binary_trees(cfg["num_vars"], cfg["depth"], cfg["num_repetitions"])
    raise ValueError(f"unknown structure {cfg['structure']!r}")


class Reference:
    """The reference EiNet over ``graph`` with K sums per region, C root
    classes and Gaussian leaves whose variance is clamped to [min_var,
    max_var].  Leaf parameters are expectation parameters (mu, E[x^2])."""

    def __init__(self, graph: Graph, K: int, num_classes: int, min_var: float,
                 max_var: float):
        self.g = graph
        self.K = K
        self.C = num_classes
        self.min_var = min_var
        self.max_var = max_var
        groups: Dict[int, List[int]] = {}
        for p, (parent, _, _) in enumerate(graph.partitions):
            groups.setdefault(self.k_out(parent), []).append(p)
        self.w_groups = sorted(groups.items())
        self.w_index = {p: (i, j) for i, (_, ps) in enumerate(self.w_groups)
                        for j, p in enumerate(ps)}

    # ------------------------------------------------------------ parameters
    def k_out(self, region: int) -> int:
        return self.C if region == self.g.root else self.K

    def init(self, key) -> dict:
        """Random valid parameters: leaf means N(0, 0.25), unit variances,
        sum weights uniform in [0.1, 1] and normalised.  Partition weights
        are held per output width, one (n, k_out, K, K) array each (see
        ``w_index``)."""
        g, K = self.g, self.K
        n_mu = len(g.entry_var) * K
        shapes = ([(len(ps), k_out, K, K) for k_out, ps in self.w_groups]
                  + [(len(g.children[r]), self.k_out(r)) for r in g.mixed])
        sizes = [2 * n_mu] + [int(np.prod(sh)) for sh in shapes]
        # one flat draw (a flat shape compiles far faster than many shaped ones)
        u = jax.random.uniform(key, (sum(sizes),))
        parts = jnp.split(u, np.cumsum(sizes)[:-1])
        u1, u2 = parts[0][:n_mu], parts[0][n_mu:]
        z = jnp.sqrt(-2.0 * jnp.log1p(-u1)) * jnp.cos(2.0 * math.pi * u2)  # Box-Muller
        mu = (0.5 * z).reshape(len(g.entry_var), K)
        w, v = [], []
        for part, sh in zip(parts[1:], shapes):
            x = 0.1 + 0.9 * part.reshape(sh)
            if len(sh) == 4:
                w.append(x / jnp.sum(x, axis=(2, 3), keepdims=True))
            else:
                v.append(x / jnp.sum(x, axis=0, keepdims=True))
        return {"mu": mu, "s": mu * mu + 1.0, "W": w, "V": v,
                "prior": jnp.full((self.C,), 1.0 / self.C, jnp.float32)}

    def weights(self, W, parts) -> jax.Array:
        """(len(parts), k_out, K, K) weights of partitions of one width."""
        where = [self.w_index[p] for p in parts]
        group = {gi for gi, _ in where}
        if len(group) != 1:
            raise ValueError("partitions of different output widths")
        return W[group.pop()][np.asarray([j for _, j in where])]

    def _var(self, mu, s):
        return jnp.clip(s - mu * mu, self.min_var, self.max_var)

    def project(self, mu, s):
        return mu, mu * mu + self._var(mu, s)

    def _einsum(self, spec, *ops):
        return jnp.einsum(spec, *ops, precision=jax.lax.Precision.HIGHEST)

    # --------------------------------------------------------------- forward
    def leaf_values(self, params, x):
        """(B, num_leaf_regions, K) leaf log-densities."""
        g = self.g
        xv = x[:, g.entry_var]  # (B, P)
        mu, s = params["mu"], params["s"]
        var = self._var(mu, s)
        d = xv[:, :, None] - mu[None]
        logp = -0.5 * jnp.log(2.0 * math.pi * var)[None] - 0.5 * d * d / var[None]
        return jax.ops.segment_sum(jnp.swapaxes(logp, 0, 1), g.entry_leaf,
                                   num_segments=len(g.leaves)).swapaxes(0, 1)

    def _partition(self, w, left, right):
        a = jnp.maximum(jnp.max(left, axis=-1, keepdims=True), NEG)
        b = jnp.maximum(jnp.max(right, axis=-1, keepdims=True), NEG)
        s = self._einsum("lkij,bli,blj->blk", w, jnp.exp(left - a), jnp.exp(right - b))
        return a + b + jnp.log(s)

    def region_values(self, W, V, leaf):
        """Every region's (B, k_out) log-density and every partition's
        (B, k_out) product-sum, from (B, leaves, K) leaf values."""
        g = self.g
        val = {r: leaf[:, i] for i, r in enumerate(g.leaves)}
        part = {}
        mix_index = {r: m for m, r in enumerate(g.mixed)}
        for level in self.g.levels:
            by_kout: Dict[int, List[int]] = {}
            for p in level:
                by_kout.setdefault(self.k_out(g.partitions[p][0]), []).append(p)
            for ps in by_kout.values():
                w = self.weights(W, ps)
                left = jnp.stack([val[g.partitions[p][1]] for p in ps], axis=1)
                right = jnp.stack([val[g.partitions[p][2]] for p in ps], axis=1)
                out = self._partition(w, left, right)
                for i, p in enumerate(ps):
                    part[p] = out[:, i]
            for p in level:
                r = g.partitions[p][0]
                kids = g.children[r]
                if r in val or any(q not in part for q in kids):
                    continue
                if len(kids) == 1:
                    val[r] = part[kids[0]]
                else:
                    v = V[mix_index[r]]  # (C_r, k_out)
                    s = jnp.stack([part[q] for q in kids], axis=1)  # (B, C_r, k)
                    a = jnp.max(s, axis=1, keepdims=True)
                    val[r] = a[:, 0] + jnp.log(jnp.sum(v[None] * jnp.exp(s - a), axis=1))
        return val, part

    def _ll_from_leaf(self, W, V, leaf, log_prior):
        val, _ = self.region_values(W, V, leaf)
        return jax.scipy.special.logsumexp(val[self.g.root] + log_prior[None], axis=-1)

    # -------------------------------------------------------------------- EM
    def statistics(self, params, x):
        """EM statistics of one block of rows (sums over the rows)."""
        g = self.g
        leaf = self.leaf_values(params, x)

        def total(W, V, lv, lp):
            return jnp.sum(self._ll_from_leaf(W, V, lv, lp))

        ll, (gW, gV, q, gp) = jax.value_and_grad(total, argnums=(0, 1, 2, 3))(
            params["W"], params["V"], leaf, jnp.log(params["prior"]))
        qe = q[:, g.entry_leaf, :]  # (B, P, K) posterior of each entry's leaf
        xv = x[:, g.entry_var]
        t = jnp.stack([xv, xv * xv], axis=-1)  # (B, P, 2)
        return {
            "nW": [w * gw for w, gw in zip(params["W"], gW)],
            "nV": [v * gv for v, gv in zip(params["V"], gV)],
            "s_t": self._einsum("bpk,bpt->pkt", qe, t),
            "s_den": jnp.sum(qe, axis=0),
            "n_class": gp,
            "ll": ll,
        }

    def m_step(self, stats, alpha: float, floor: float):
        W = []
        for n in stats["nW"]:
            n = jnp.maximum(n + alpha, floor)
            W.append(n / jnp.sum(n, axis=(-2, -1), keepdims=True))
        V = []
        for n in stats["nV"]:
            n = jnp.maximum(n + alpha, floor)
            V.append(n / jnp.sum(n, axis=0, keepdims=True))
        den = jnp.maximum(stats["s_den"], floor)
        mu, s = self.project(stats["s_t"][..., 0] / den, stats["s_t"][..., 1] / den)
        prior = stats["n_class"] + alpha
        return {"mu": mu, "s": s, "W": W, "V": V, "prior": prior / jnp.sum(prior)}

    def blend(self, old, new, lam: float):
        out = jax.tree_util.tree_map(lambda o, n: (1.0 - lam) * o + lam * n, old, new)
        out["mu"], out["s"] = self.project(out["mu"], out["s"])
        return out
