"""Operations and bytes an EiNet needs per row, counted from the shapes of
the benchmark's own region graph, whatever implements the layers.

Counted per row (multiply and add each one operation):

* leaf layer: 4 per Gaussian log-density (subtract the mean, square,
  scale by the precision, add the normaliser), one per (leaf entry, k);
* leaf region sums: one add per (leaf entry, k);
* each partition: 2 * k_out * K * K (the K x K outer product contracted
  with its weights; exp and log are not counted);
* each mixed region: 2 * children * k_out.

An EM step per row is the forward pass, twice each partition's and mixing
layer's work again for the backward pass (weight and input gradients), and
5 per (leaf entry, k) for the leaf statistics (x and x^2 weighted by the
posterior, and the posterior sum).  The M-step is per parameter, not per
row, and is left out.

Bytes per row are the row's input (4 per variable) plus each region's K
float32 log-densities written once and read once.
"""

from __future__ import annotations

from typing import Dict


def counts(graph, K: int, num_classes: int) -> Dict[str, float]:
    entries = len(graph.entry_var)
    leaf = 4 * entries * K
    leaf_sum = entries * K
    part = 0
    for parent, _, _ in graph.partitions:
        k_out = num_classes if parent == graph.root else K
        part += 2 * k_out * K * K
    mix = 0
    for r in graph.mixed:
        k_out = num_classes if r == graph.root else K
        mix += 2 * len(graph.children[r]) * k_out
    forward = leaf + leaf_sum + part + mix
    regions = len(graph.regions)
    return {
        "forward_flops": float(forward),
        "train_flops": float(forward + 2 * (part + mix) + 5 * entries * K),
        "bytes": float(4 * graph.num_vars + 8 * K * regions),
    }

