"""Serving-engine throughput benchmark -> BENCH_serve.json.

Measures the batched exact-inference engine (``repro.serve``) against the
direct one-call-at-a-time path on a mixed query stream and writes a JSON
record so the perf trajectory has data across PRs:

  PYTHONPATH=src python benchmarks/bench_serve.py --smoke     # CI-sized
  PYTHONPATH=src python benchmarks/bench_serve.py             # einet_rat

Schema (one flat dict): see ``repro.serve.benchmark.run_benchmark`` plus
{"arch", "num_vars", "num_sums", "timestamp"}.
"""

from __future__ import annotations

import argparse
import datetime
import json

import jax

from repro.configs import EinetConfig, get_config
from repro.launch.cells import build_einet
from repro.obs import slo as slo_lib
from repro.serve import format_report, mixed_requests, run_benchmark

SMOKE_CONFIG = EinetConfig(
    name="einet-rat-serve-smoke",
    structure="rat",
    # 32 vars: the smallest RAT shape whose scopes don't collide across
    # repetitions, so the whole circuit depth-groups and the smoke run
    # exercises the grouped execution path (see bench_train.SMOKE_CONFIG)
    num_vars=32,
    depth=2,
    num_repetitions=2,
    num_sums=4,
    batch_size=64,
)

PD_SMOKE_CONFIG = EinetConfig(
    name="einet-pd-serve-smoke",
    structure="pd",
    # 32 vars as a 4x8 image, delta=2 on both axes: the interior PD pairs
    # compile to one gather-grouped segment, so the smoke run serves
    # through the gather kernels (see bench_train.PD_SMOKE_CONFIG)
    height=4,
    width=8,
    num_channels=1,
    delta=2,
    pd_axes=("h", "w"),
    num_sums=4,
    batch_size=64,
)


def _bench_one(cfg, requests: int, max_batch: int, reps: int,
               smoke: bool) -> dict:
    model = build_einet(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = mixed_requests(model.num_vars, requests, seed=0)
    report = run_benchmark(model, params, reqs, max_batch=max_batch, reps=reps)
    parity_ok = report["parity_max_abs_diff"] <= 1e-5
    # LL serving must run the grouped plan -- RAT through fused (canonical)
    # segments, PD through gather segments (sampling keeps the per-layer
    # cache path by design).  The historical PD structural exemption is
    # gone: gather fusion covers it now.
    grouped_ok = model.grouped_active
    report.update(
        arch=cfg.name,
        num_vars=model.num_vars,
        num_sums=model.K,
        smoke=smoke,
        parity_ok=parity_ok,
        grouped_ok=grouped_ok,
        # kernel launches per forward: per-layer loop vs grouped plan
        # (includes the effective vmem_budget the planner resolved)
        grouping=model.grouping_summary(),
        timestamp=datetime.datetime.now(datetime.timezone.utc).isoformat(),
    )
    print(format_report(report))
    g = report["grouping"]
    print(f"grouping  : launches {g['launches_per_layer']} -> "
          f"{g['launches_grouped']} ({g['fused_groups']} fused + "
          f"{g['gather_groups']} gather group(s) over "
          f"{g['fused_pairs']}/{g['num_pairs']} pairs)")
    if not parity_ok:
        print(f"PARITY FAILURE: {report['parity_max_abs_diff']:.2e} > 1e-5")
    if not grouped_ok:
        print("GROUPED-EXECUTION FAILURE: arch expected to depth-group fell "
              "back to the per-layer path")
    return report


def main(
    smoke: bool = False,
    arch: str = "einet_rat",
    requests: int = 64,
    max_batch: int = 0,
    reps: int = 3,
    out: str = "BENCH_serve.json",
) -> dict:
    cfg = SMOKE_CONFIG if smoke else get_config(arch)
    if smoke:
        requests = min(requests, 24)
    report = _bench_one(cfg, requests, max_batch, reps, smoke)
    ok = report["parity_ok"] and report["grouped_ok"]
    if smoke:
        # the gather-topology twin: CI serves through the PD gather kernels
        pd_report = _bench_one(PD_SMOKE_CONFIG, requests, max_batch, reps,
                               smoke)
        report["pd_smoke"] = pd_report
        ok = ok and pd_report["parity_ok"] and pd_report["grouped_ok"]
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"wrote {out}")
        print(f"history -> {slo_lib.append_history('serve', report)}")
    return report if ok else {}


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + short stream (CI profile)")
    ap.add_argument("--arch", default="einet_rat")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=0)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    result = main(smoke=args.smoke, arch=args.arch, requests=args.requests,
                  max_batch=args.max_batch, reps=args.reps, out=args.out)
    raise SystemExit(0 if result else 1)
