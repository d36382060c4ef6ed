"""One run of one cell: set-up, the measured window, the comparison, and
the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the line's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the profiler records the first seconds of the window and
the metrics are the cell's per-layer metrics, with ``busy_s``, ``window_s``
and a ``breakdown``.  The numbers ``correct`` compares come last, in the
line under ``checks`` and as the last lines on standard error.

The driver is found by name: a traffic mix's ``driver`` names the module
``bench/harness/<driver>.py`` (:meth:`harness.core.Cell.driver`), so a new
driver and a traffic file that names it run a cell with no edit here.  A
driver keeps this contract:

* ``run(cell, seed, seconds, tracer, counter, t_process, devs) -> dict``:
  set-up from the seed, then the window of ``seconds``, then the
  comparison with the reference.  ``tracer`` (:class:`harness.core.Tracer`)
  is started just before the window (``tracer.start()``), opened as its
  first act (``open_window()``), ticked once per step (``tick()``) and
  stopped once the window's work is done (``stop()``); ``counter``
  (:class:`harness.core.CompileCounter`) is ``active`` over the window
  alone; ``devs`` are the cell's chips.
* Where ``tracer.on``, set-up hands the tracer the compiled HLO text of
  each program that the window runs, before the window
  (``tracer.add_program(compiled.as_text())``), so the traced run charges
  every device op to its named scope.  Where it is off, the driver does
  nothing more than an untraced run needs.
* Keys every driver returns: ``kind`` (what the readers switch on, such as
  ``train``), ``e2e`` (the cell's end-to-end metrics by name), ``attempted``
  and ``failed`` (counts of the window's work), ``checks``
  (:class:`harness.core.Checks`) and ``memory_peak_bytes``.
* Keys the per-layer readers use, where the kind has them: ``batch`` (rows
  per step), ``work`` (:func:`harness.work.counts`) and
  ``input_ms_per_step``.  This module adds ``chips``, ``peak``, ``trace``
  (:func:`harness.core.reduce_window` of the traced run, else None) and
  ``compiles_in_window``.
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
    run = cell.driver().run(cell, seed, seconds, tracer, counter, t_process, devs)
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
        result["breakdown"] = breakdown(reduced)
    result["checks"] = run["checks"].table()
    return result


def breakdown(reduced: Dict) -> Dict:
    """The ten device ops that took most time, each named with its program
    scope where it has one, and the ten longest shares of idle time by the
    innermost span, the benchmark's or the program's, that the host was in."""
    ops = [[f"{op} ({reduced['op_scopes'][op]})" if reduced["op_scopes"].get(op) else op, s]
           for op, s in reduced["device_ops"]]
    return {"device_ops": ops, "idle_gaps": reduced["idle_split"][:10]}


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
