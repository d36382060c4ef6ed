"""One run of one cell: set-up, the measured window, the comparison, and
the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the line's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the profiler records the first seconds of the window and
the metrics are the cell's per-layer metrics, with ``busy_s``, ``window_s``
and a ``breakdown``.  The numbers ``correct`` compares come last, in the
line under ``checks`` and as the last lines on standard error.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from typing import Dict, List, Optional

from harness import core

TRACE_SECONDS = 2.0


def parse(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(cell: core.Cell, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True) -> Dict:
    """Run one cell and return the result object (the last line)."""
    import jax

    devs = core.devices(cell.chips, require_tpu)
    kind = devs[0].device_kind
    peak = core.peaks(kind) if require_tpu else None
    if require_tpu:
        core.enable_compile_cache()
    counter = core.CompileCounter()
    tracer = core.Tracer(trace, min(TRACE_SECONDS, seconds))
    driver = cell.traffic["driver"]
    if driver != "train":
        raise KeyError(f"unknown driver {driver!r}")
    from harness import train as drv

    run = drv.run(cell, seed, seconds, tracer, counter, t_process, devs)
    core.log(f"compiles inside the window: {counter.count} {counter.names[:6]}; "
             f"persistent cache over the run: {counter.cache}")
    run["compiles_in_window"] = counter.count
    run["chips"] = cell.chips
    run["peak"] = peak
    reduced = tracer.reduce() if trace else None
    run["trace"] = reduced

    metrics: Dict[str, Dict] = {}
    if not trace:
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = {"value": run["e2e"][m["name"]], "unit": m["unit"]}
    else:
        for m in cell.metrics("per_layer"):
            value = core.load_module(cell.bench / "metrics" / f"{m['name']}.py").read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind, "count": len(jax.devices()),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": run["checks"].ok() and run["failed"] == 0,
              "attempted": run["attempted"], "failed": run["failed"],
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = run["checks"].table()
    return result


def main(t_process: float, argv: Optional[List[str]] = None) -> int:
    args = parse(argv)
    if not math.isfinite(args.seconds) or args.seconds <= 0:
        raise SystemExit("--seconds must be positive")
    try:
        cell = core.Cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_process)
    except core.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
