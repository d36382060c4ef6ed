"""Mixture-of-EiNets training benchmark -> BENCH_mixture.json.

The mixture subsystem's headline claim: training C architecturally-identical
components is better executed as ONE vmapped, jitted EM step over a stacked
``(C, B, D)`` batch than as a Python loop of C single-model steps -- the
batched-circuit-execution observation of "Scaling Tractable Probabilistic
Circuits: A Systems Perspective" (PyJuice) applied to whole models.  Both
paths compute the identical update (per-cluster hard EM, ``repro.mixture``),
so per-component parameter parity after a step is the benchmark's gate and
the wall-clock ratio is the result:

  PYTHONPATH=src python benchmarks/bench_mixture.py --smoke   # CI, parity-gated
  PYTHONPATH=src python benchmarks/bench_mixture.py           # C in {4, 8}

Exit status is the parity gate (the timing is recorded, not gated, so CI
stays robust to timer noise).
"""

from __future__ import annotations

import argparse
import datetime
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import EinetConfig
from repro.launch.cells import build_einet
from repro.mixture import EiNetMixture, MixtureTrainConfig, make_mixture_em_step
from repro.obs import slo as slo_lib
from repro.train import TrainConfig, make_em_step

# one CPU-feasible component in the dispatch-bound regime the mixture step
# targets: many components, each small enough that the Python loop's
# per-component dispatch is a real fraction of its step.  (At container-CPU
# scale a LARGE component turns compute-bound and XLA-CPU threads the looped
# steps to parity -- recorded in EXPERIMENTS.md §Perf; on TPU the vmapped
# step additionally saves C-1 program launches per update.)  Paper-scale
# components need TPU; shapes are in the JSON so numbers are comparable.
COMPONENT_CONFIG = EinetConfig(
    name="einet-rat-mixture-bench",
    structure="rat",
    num_vars=32,
    depth=2,
    num_repetitions=2,
    num_sums=4,
    batch_size=32,
)

SMOKE_CONFIG = EinetConfig(
    name="einet-rat-mixture-smoke",
    structure="rat",
    num_vars=16,
    depth=2,
    num_repetitions=2,
    num_sums=4,
    batch_size=32,
)

# (cell id, num components, per-component batch, timed steps); C spans the
# paper's clusters-of-images regime (§4.2 uses on the order of tens of
# clusters)
DEFAULT_CELLS = (
    ("mixture_c4", 4, 32, 4),
    ("mixture_c16", 16, 32, 4),
    ("mixture_c32", 32, 32, 4),
)

PARITY_TOL = 1e-6  # vmap-vs-loop reassociates reductions; ~1e-9 in practice


def _component(params, c):
    return jax.tree_util.tree_map(lambda a: a[c], params["components"])


def _block(tree):
    jax.block_until_ready(jax.tree_util.tree_leaves(tree)[0])


def bench_cell(cell: str, cfg: EinetConfig, num_components: int, batch: int,
               steps: int, reps: int) -> dict:
    base = build_einet(cfg)
    mix = EiNetMixture(base, num_components)
    params = mix.init(jax.random.PRNGKey(0))
    d = base.num_vars
    x = jnp.asarray(
        np.random.RandomState(0)
        .randn(num_components, batch, d).astype(np.float32)
    )

    # donate=False: both paths re-feed the same params across timing reps
    vstep = make_mixture_em_step(mix, MixtureTrainConfig(donate=False))
    sstep = make_em_step(base, TrainConfig(donate=False))

    # -- parity: one vmapped step vs the loop, from identical init ---------
    pv, ll_v = vstep(params, x)
    _block(pv)
    looped = [_component(params, c) for c in range(num_components)]
    looped = [sstep(p, x[c])[0] for c, p in enumerate(looped)]
    _block(looped)
    parity = 0.0
    for c in range(num_components):
        a_leaves = jax.tree_util.tree_leaves(_component(pv, c))
        b_leaves = jax.tree_util.tree_leaves(looped[c])
        for a, b in zip(a_leaves, b_leaves):
            if np.asarray(a).size:
                parity = max(parity, float(
                    np.max(np.abs(np.asarray(a) - np.asarray(b)))
                ))

    # -- timing ------------------------------------------------------------
    def run_vmapped():
        p = params
        for _ in range(steps):
            p, _ll = vstep(p, x)
        _block(p)

    def run_looped():
        comps = [_component(params, c) for c in range(num_components)]
        for _ in range(steps):
            for c in range(num_components):
                comps[c], _ll = sstep(comps[c], x[c])
        _block(comps)

    run_vmapped()  # steady-state warm-up for both programs
    run_looped()
    best_v = best_l = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run_vmapped()
        best_v = min(best_v, (time.perf_counter() - t0) / steps)
        t0 = time.perf_counter()
        run_looped()
        best_l = min(best_l, (time.perf_counter() - t0) / steps)

    return {
        "cell": cell,
        "component_arch": cfg.name,
        "num_components": num_components,
        "num_vars": d,
        "num_sums": base.K,
        "num_params_m": round(mix.num_params(params) / 1e6, 3),
        "per_component_batch": batch,
        "steps_timed": steps,
        "vmapped_ms_per_step": round(best_v * 1e3, 2),
        "looped_ms_per_step": round(best_l * 1e3, 2),
        "speedup": round(best_l / best_v, 3),
        "param_parity_max_abs_diff": parity,
        "param_parity_ok": parity <= PARITY_TOL,
    }


def main(smoke: bool = False, components: int = 0, batch: int = 0,
         steps: int = 0, reps: int = 2,
         out: str = "BENCH_mixture.json") -> dict:
    if smoke:
        cells = [("smoke", SMOKE_CONFIG, components or 4, batch or 32, 2)]
        reps = 1
    else:
        cells = [
            (cell, COMPONENT_CONFIG, components or c, batch or b, steps or s)
            for cell, c, b, s in DEFAULT_CELLS
        ]
    results = []
    for cell, cfg, c, b, s in cells:
        print(f"[bench_mixture] {cell}: C={c} batch={b}/component ...")
        r = bench_cell(cell, cfg, c, b, s, reps)
        print(
            f"  vmapped {r['vmapped_ms_per_step']:.1f} ms/step vs looped "
            f"{r['looped_ms_per_step']:.1f} ms/step (x{r['speedup']:.2f}); "
            f"param parity {r['param_parity_max_abs_diff']:.2e}"
        )
        results.append(r)
    parity_ok = all(r["param_parity_ok"] for r in results)
    report = {
        "results": results,
        "smoke": smoke,
        "backend": jax.default_backend(),
        "parity_ok": parity_ok,
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(),
    }
    if not parity_ok:
        print(f"PARAM PARITY FAILURE (> {PARITY_TOL})")
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"wrote {out}")
        print(f"history -> {slo_lib.append_history('mixture', report)}")
    return report if parity_ok else {}


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny component, parity-gated only (CI profile)")
    ap.add_argument("--components", type=int, default=0,
                    help="override C for every cell")
    ap.add_argument("--batch", type=int, default=0,
                    help="override the per-component batch")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--out", default="BENCH_mixture.json")
    args = ap.parse_args()
    result = main(smoke=args.smoke, components=args.components,
                  batch=args.batch, steps=args.steps, reps=args.reps,
                  out=args.out)
    raise SystemExit(0 if result else 1)
