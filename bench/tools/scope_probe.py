"""Run one cell with the profiler and ``repro.obs`` tracing on over the
window, and read its trace two ways: as the accepted per-layer metrics read
it (``harness/trace.py``), and by the program's own spans and named scopes
(``harness/scopes.py``).

    python3 bench/tools/scope_probe.py --workload pd_svhn.em_b512 --seed 7 --seconds 10

``--obs 0`` leaves ``repro.obs`` tracing off (the profiler alone, as a
``--trace 1`` run of ``bench/run.py``), to read what the program's spans
cost.  Prints a summary and writes it, with the scope of every device op
(from the step's compiled HLO text), to
``chiprun_out/scope_probe_<cell>_<seed>.json``.
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
from harness import train as drv  # noqa: E402
from harness.main import TRACE_SECONDS  # noqa: E402

class ObsTracer(core.Tracer):
    """The benchmark's tracer, with ``repro.obs`` tracing on exactly while
    the profiler runs."""

    def __init__(self, seconds: float, obs_on: bool):
        super().__init__(True, seconds)
        self.obs_on = obs_on

    def start(self):
        super().start()
        if self.obs_on:
            from repro import obs

            obs.configure(trace=True)

    def stop(self):
        if self.running and self.obs_on:
            from repro import obs

            obs.configure(trace=False)
        super().stop()


def hlo_scopes(cell):
    """Op name -> scope, from the compiled HLO text of the cell's EM step."""
    import jax
    import jax.numpy as jnp

    from repro.launch import cells as cells_lib
    from repro.train import TrainConfig, make_em_step

    model = cells_lib.build_einet(core.program_config(cell.config))
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.zeros((cell.traffic["batch"], model.num_vars), jnp.float32)
    return scopes.hlo_op_scopes(
        make_em_step(model, TrainConfig()).lower(params, x).compile().as_text())


def read_accepted(cell, run, reduced):
    """The accepted per-layer metrics of the cell, read from ``reduced``."""
    run = dict(run, trace=reduced)
    return {m["name"]: core.load_module(cell.bench / "metrics" / f"{m['name']}.py").read(run)
            for m in cell.metrics("per_layer")}


def probe(cell, seed: int, seconds: float, obs_on: bool = True,
          require_tpu: bool = True, t_process: float = T_PROCESS) -> dict:
    devs = core.devices(cell.chips, require_tpu)
    if require_tpu:
        core.enable_compile_cache()
    counter = core.CompileCounter()
    tracer = ObsTracer(min(TRACE_SECONDS, seconds), obs_on)
    run = drv.run(cell, seed, seconds, tracer, counter, t_process, devs)
    core.log(f"compiles inside the window: {counter.count} {counter.names[:6]}")
    path = trace.find_xplane(tracer.dir)
    run.update(chips=cell.chips, peak=core.peaks(devs[0].device_kind) if require_tpu else None)

    # the accepted reduction, on the accepted flattening and on the new one
    old = trace.events_from_xplane(path)
    window = trace.spans(old, "bench.window")[0]
    accepted = read_accepted(cell, run, trace.reduce_trace(old, window))

    op_scopes = hlo_scopes(cell) if require_tpu else {}
    events = scopes.events_from_xplane(path, op_scopes)
    red = scopes.reduce(events, window)
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
        "idle_split": red["idle_split"], "idle_gaps_accepted": red["idle_gaps"],
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
