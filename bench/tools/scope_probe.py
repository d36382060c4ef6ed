"""Run one cell with the benchmark's tracer (the profiler and ``repro.obs``
tracing on over the window), and print what its trace holds beyond the
result line: the accepted per-layer metrics read from the accepted
flattening (``harness/trace.py``, no program spans) beside the benchmark's
(``harness/scopes.py``), the share of device time the layers cover, the
idle split, and the ops under no layer's scope.

    python3 bench/tools/scope_probe.py --workload pd_svhn.em_b512 --seed 7 --seconds 10

``--obs 0`` turns the tracer's ``repro.obs`` switch off (the profiler
alone), to read what the program's spans cost.  Prints a summary and writes
it, with the scope of every device op (from the step's compiled HLO text),
to ``chiprun_out/scope_probe_<cell>_<seed>.json``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.join(_ROOT, "bench")]

from harness import core, scopes, trace  # noqa: E402
from harness.main import TRACE_SECONDS  # noqa: E402


# the per-layer metrics that read the accepted reduction (harness/trace.py)
ACCEPTED = ("train_input_ms", "train_mfu", "train_idle_share")


def read_accepted(cell, run, reduced):
    """The cell's per-layer metrics of :data:`ACCEPTED`, read from ``reduced``."""
    run = dict(run, trace=reduced)
    return {m["name"]: core.load_module(cell.bench / "metrics" / f"{m['name']}.py").read(run)
            for m in cell.metrics("per_layer") if m["name"] in ACCEPTED}


def probe(cell, seed: int, seconds: float, obs_on: bool = True,
          require_tpu: bool = True, t_process: float = T_PROCESS) -> dict:
    devs = core.devices(cell.chips, require_tpu)
    if require_tpu:
        core.enable_compile_cache()
    counter = core.CompileCounter()
    tracer = core.Tracer(True, min(TRACE_SECONDS, seconds), obs=obs_on)
    run = cell.driver().run(cell, seed, seconds, tracer, counter, t_process, devs)
    core.log(f"compiles inside the window: {counter.count} {counter.names[:6]}")
    run.update(chips=cell.chips, peak=core.peaks(devs[0].device_kind) if require_tpu else None)

    # the accepted metrics, on the accepted flattening and on the benchmark's
    op_scopes = tracer.op_scopes
    events = tracer.events()
    red = core.reduce_window(events)
    window = trace.spans(events, "bench.window")[0]
    plain = [e[:5] for e in events if not scopes.is_program_span(e[2])]
    accepted = read_accepted(cell, run, trace.reduce_trace(plain, window))
    layers = scopes.layer_ms(red)
    steps = red["steps"]
    busy_ms = 1e3 * red["busy_s"] / steps if steps else None
    covered = sum(layers.get(f"train_{k}_ms", 0.0) for k in scopes.LAYERS)
    uncovered = collections.Counter()
    for op, sec in red["op_seconds"].items():
        scope = red["op_scopes"].get(op, "")
        if not any(scope in s for s in scopes.LAYERS.values()):
            uncovered[f"{op} [{scope or '-'}]"] += sec
    return {
        "workload": cell.name, "seed": seed, "obs": int(obs_on),
        "correct": run["checks"].ok() and run["failed"] == 0,
        "checks": run["checks"].table(),
        "setup_s": run["e2e"]["setup_s"],
        "train_examples_per_s": run["e2e"]["train_examples_per_s"],
        "traced_steps_per_s": steps / red["window_s"] if red["window_s"] else None,
        "accepted": accepted,
        "accepted_on_new_flattening": read_accepted(cell, run, red),
        "layers_ms": layers, "busy_ms_per_step": busy_ms,
        "covered_share": covered / busy_ms if busy_ms else None,
        "idle_s": red["window_s"] - red["busy_s"],
        "idle_split": red["idle_split"],
        "scope_seconds": red["scope_seconds"], "span_seconds": red["span_seconds"],
        "steps": steps,
        "device_ops": [[op, sec, red["op_scopes"].get(op, "")]
                       for op, sec in red["device_ops"]],
        "uncovered_ops": uncovered.most_common(12),
        "op_scopes": op_scopes,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--obs", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()
    out = probe(core.Cell(args.workload), args.seed, args.seconds, bool(args.obs))
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    dest = os.path.join(_ROOT, "chiprun_out", f"scope_probe_{args.workload}_{args.seed}.json")
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    brief = {k: v for k, v in out.items() if k not in ("op_scopes", "scope_seconds", "checks")}
    print(json.dumps(brief), flush=True)


if __name__ == "__main__":
    sys.exit(main())
