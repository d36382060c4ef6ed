"""Evaluation-throughput benchmark -> BENCH_eval.json.

Measures the engine-streamed held-out-LL path (``repro.eval.metrics``)
against the engine-free dense baseline (fixed-size jitted ``EiNet.query``
chunks) on the same test rows, so EXPERIMENTS.md records what serving the
benchmark through the production engine costs (or saves) versus a bespoke
eval loop -- plus the inpainting harness throughput, parity-gated:

  PYTHONPATH=src python benchmarks/bench_eval.py --smoke    # CI profile
  PYTHONPATH=src python benchmarks/bench_eval.py            # 16x16x3 PD net
"""

from __future__ import annotations

import argparse
import datetime
import json
import time

import jax
import numpy as np

from repro.data import datasets as ds_lib
from repro.eval.inpainting import run_inpainting
from repro.eval.metrics import direct_log_likelihoods, engine_log_likelihoods
from repro.eval.workbench import EvalConfig, pd_config_for
from repro.launch.cells import build_einet
from repro.obs import slo as slo_lib
from repro.serve import ServeEngine


def main(smoke: bool = False, rows: int = 512, inpaint_rows: int = 8,
         max_batch: int = 64, out: str = "BENCH_eval.json") -> dict:
    cfg = EvalConfig(dataset="synthetic", smoke=smoke)
    if smoke:
        rows, inpaint_rows, max_batch = 96, 4, 16
    dataset = (
        ds_lib.synthetic_image_dataset(8, 8, 1, num_train=256, num_test=rows)
        if smoke else
        ds_lib.synthetic_image_dataset(16, 16, 3, num_train=256, num_test=rows)
    )
    spec = dataset.spec
    model = build_einet(pd_config_for(cfg, spec))
    params = model.init(jax.random.PRNGKey(0))
    test_x, _ = ds_lib.to_domain(dataset.test_x, "normal")
    x = test_x[:rows]

    engine = ServeEngine(model, params, max_batch=max_batch)
    res = engine_log_likelihoods(
        model, params, x, engine=engine, parity_rows=min(64, rows)
    )

    # dense baseline: compile once on the chunk shape, then measure
    direct_log_likelihoods(model, params, x[: max_batch * 2], chunk=max_batch)
    t0 = time.perf_counter()
    ll_direct = direct_log_likelihoods(model, params, x, chunk=max_batch)
    direct_s = time.perf_counter() - t0

    inp = run_inpainting(
        model, params, x[:inpaint_rows], spec.height, spec.width,
        spec.channels, engine=engine, parity_rows=None,
    )

    mismatches = res.parity_mismatches + inp.metrics["parity_mismatches"]
    report = {
        "arch": f"einet-pd-{spec.name}-eval",
        "num_vars": model.num_vars,
        "num_sums": model.K,
        "smoke": smoke,
        "rows": rows,
        "engine_rows_per_s": res.rows_per_second,
        "engine_seconds": res.engine_seconds,
        "engine_warmup_s": res.warmup_seconds,
        "direct_rows_per_s": rows / max(direct_s, 1e-9),
        "direct_seconds": direct_s,
        "engine_vs_direct": (rows / max(res.engine_seconds, 1e-9))
        / (rows / max(direct_s, 1e-9)),
        "ll_max_abs_diff_engine_vs_direct": float(
            np.max(np.abs(res.ll - ll_direct))
        ),
        "inpaint_requests_per_s": inp.metrics["requests_per_s"],
        "inpaint_requests": inp.metrics["num_requests"],
        "parity_mismatches": int(mismatches),
        "parity_ok": mismatches == 0,
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(),
    }
    print(f"{report['arch']}: engine {report['engine_rows_per_s']:.0f} rows/s "
          f"vs dense {report['direct_rows_per_s']:.0f} rows/s "
          f"(x{report['engine_vs_direct']:.2f}); inpainting "
          f"{report['inpaint_requests_per_s']:.0f} req/s; "
          f"parity mismatches {mismatches}")
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"wrote {out}")
        print(f"history -> {slo_lib.append_history('eval', report)}")
    return report if mismatches == 0 else {}


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--inpaint-rows", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--out", default="BENCH_eval.json")
    args = ap.parse_args()
    result = main(smoke=args.smoke, rows=args.rows,
                  inpaint_rows=args.inpaint_rows, max_batch=args.max_batch,
                  out=args.out)
    raise SystemExit(0 if result else 1)
