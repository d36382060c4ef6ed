"""Fig. 6 (supplementary): inference time per sample, EiNet vs naive,
sweeping K / D / R.  Same protocol as bench_fig3 but timing
``log_likelihood`` on a 100-sample test batch (the paper's setup).

CSV: impl,param,value,inference_us_per_sample
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import EiNet, NaiveEiNet, Normal, random_binary_trees

DVARS, NTEST = 128, 100
DEFAULTS = dict(depth=3, reps=4, k=8)


def one(impl: str, depth: int, reps: int, k: int) -> float:
    g = random_binary_trees(DVARS, depth, reps, seed=0)
    cls = NaiveEiNet if impl == "naive" else EiNet
    net = cls(g, num_sums=k, exponential_family=Normal())
    params = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (NTEST, DVARS))
    f = jax.jit(net.log_likelihood)
    jax.block_until_ready(f(params, x))  # compile
    t0 = time.time()
    for _ in range(5):
        out = f(params, x)
    jax.block_until_ready(out)
    return (time.time() - t0) / 5 / NTEST * 1e6


def run(quick: bool = False):
    rows = []
    ks = [4, 16] if quick else [2, 4, 8, 16, 24]
    depths = [2, 4] if quick else [1, 2, 3, 4, 5]
    reps = [2, 8] if quick else [1, 4, 8, 16]
    for impl in ("einet", "naive"):
        for k in ks:
            rows.append((impl, "K", k, one(impl, DEFAULTS["depth"], DEFAULTS["reps"], k)))
        for d in depths:
            rows.append((impl, "D", d, one(impl, d, DEFAULTS["reps"], DEFAULTS["k"])))
        for r in reps:
            rows.append((impl, "R", r, one(impl, DEFAULTS["depth"], r, DEFAULTS["k"])))
    return rows


def main(quick: bool = False):
    rows = run(quick)
    print("impl,param,value,inference_us_per_sample")
    for r in rows:
        print(f"{r[0]},{r[1]},{r[2]},{r[3]:.2f}")
    return rows


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
