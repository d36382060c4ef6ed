"""Fig. 3: training time + peak memory, EiNet vs naive implementation,
sweeping the structural hyper-parameters K (densities per sum/leaf),
D (split depth), R (replica).

The paper's measurement on a RTX 2080 Ti shows 1-2 orders of magnitude;
this container is a single CPU core, so magnitudes differ but the *scaling
claim* (EiNet time/memory grows gracefully in K while the naive
K^3-exp/materialized-product implementation blows up) is measurable.

Memory proxy (no GPU allocator here): peak live buffer bytes from the jitted
step's compiled memory_analysis (temp + output), which is exactly the
materialized-products effect the paper plots.

CSV: impl,param,value,train_s_per_epoch,peak_temp_mb
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EiNet, NaiveEiNet, Normal, em_update, random_binary_trees

N, DVARS = 512, 128  # paper: 2000 x 512 (scaled to CPU)
DEFAULTS = dict(depth=3, reps=4, k=8)


def one(impl: str, depth: int, reps: int, k: int):
    g = random_binary_trees(DVARS, depth, reps, seed=0)
    cls = NaiveEiNet if impl == "naive" else EiNet
    net = cls(g, num_sums=k, exponential_family=Normal())
    params = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (N, DVARS))
    step = jax.jit(lambda p, b: em_update(net, p, b))
    lowered = step.lower(params, x)
    ma = lowered.compile().memory_analysis()
    peak_mb = (ma.temp_size_in_bytes + ma.output_size_in_bytes) / 1e6
    p, _ = step(params, x)  # compile+warm
    t0 = time.time()
    reps_t = 3
    for _ in range(reps_t):
        p, ll = step(p, x)
    jax.block_until_ready(ll)
    return (time.time() - t0) / reps_t, peak_mb


def run(quick: bool = False):
    rows = []
    ks = [4, 8, 16] if quick else [2, 4, 8, 16, 24]
    depths = [2, 4] if quick else [1, 2, 3, 4, 5]
    reps = [2, 8] if quick else [1, 4, 8, 16]
    for impl in ("einet", "naive"):
        for k in ks:
            t, m = one(impl, DEFAULTS["depth"], DEFAULTS["reps"], k)
            rows.append((impl, "K", k, t, m))
        for d in depths:
            t, m = one(impl, d, DEFAULTS["reps"], DEFAULTS["k"])
            rows.append((impl, "D", d, t, m))
        for r in reps:
            t, m = one(impl, DEFAULTS["depth"], r, DEFAULTS["k"])
            rows.append((impl, "R", r, t, m))
    return rows


def main(quick: bool = False):
    rows = run(quick)
    print("impl,param,value,train_s_per_epoch,peak_temp_mb")
    for r in rows:
        print(f"{r[0]},{r[1]},{r[2]},{r[3]:.4f},{r[4]:.2f}")
    # derived: speedup + memory ratio at the largest K
    kmax = max(r[2] for r in rows if r[1] == "K")
    te = [r for r in rows if r[0] == "einet" and r[1] == "K" and r[2] == kmax][0]
    tn = [r for r in rows if r[0] == "naive" and r[1] == "K" and r[2] == kmax][0]
    print(f"# K={kmax}: naive/einet time {tn[3]/te[3]:.1f}x, "
          f"memory {tn[4]/max(te[4],1e-9):.1f}x")
    return rows


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
