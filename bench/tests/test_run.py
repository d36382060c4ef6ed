"""Whole runs of tiny cells on the CPU: the last line's schema, the chip
check, ``correct`` coming out false under each fault a cell can have,
drivers found by name, and what a traced run adds to an untraced one."""

import json
import os
import pathlib
import subprocess
import sys
import time

import pytest
from harness import core, main, scopes

TESTS = pathlib.Path(__file__).resolve().parent
BENCH = TESTS.parent
ROOT = BENCH.parent

SCRIPT = """
import json, pathlib, sys, time
sys.path[:0] = {paths!r}
import conftest, plants
from harness import core, main, scopes
if {plant!r}:
    plants.PLANTS[{plant!r}]()
bench = conftest.make_tree(pathlib.Path({tmp!r}))
r = main.run_cell(core.Cell({cell!r}, bench=bench), 2**31 + 77, 1.0, False,
                  time.perf_counter(), require_tpu=False)
print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
"""


def run_planted(tmp_path, cell, plant, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    paths = [str(ROOT / "src"), str(BENCH), str(BENCH / "configs"), str(TESTS)]
    code = SCRIPT.format(paths=paths, plant=plant, tmp=str(tmp_path), cell=cell)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,plant,devices,correct", [
    ("pd.train", None, 1, True),
    ("pd.train", "state_unchanged", 1, False),
    ("pd.train", "half_batch", 1, False),
    ("rat.train", "half_batch", 1, False),
    ("pd.dp2", None, 2, True),
    ("pd.dp2", "no_exchange", 2, False),
    ("pd.dp2", "half_batch", 2, False),
])
def test_correct_under_faults(tmp_path, cell, plant, devices, correct):
    r = run_planted(tmp_path, cell, plant, devices)
    assert r["correct"] is correct, r["checks"]


def test_last_line_schema(tiny_bench):
    cell = core.Cell("pd.train", bench=tiny_bench)
    for trace in (False, True):
        r = main.run_cell(cell, 5, 0.5, trace, time.perf_counter(), require_tpu=False)
        assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
        assert list(r)[-1] == "checks"
        assert set(r["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
        names = {m["name"] for m in cell.metrics("per_layer" if trace else "end_to_end")}
        assert set(r["metrics"]) <= names
        for m in r["metrics"].values():
            assert set(m) == {"value", "unit"}
        for c in r["checks"].values():
            assert set(c) == {"value", "limit"}
        if trace:
            assert {"busy_s", "window_s"} <= set(r["device"])
            assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
        else:
            assert {"train_examples_per_s", "setup_s"} == set(r["metrics"])
        json.dumps(r)


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload", "pd_svhn.em_b512",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=str(ROOT), timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cells_find_their_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = core.Cell(w["name"])
        assert cell.traffic["driver"] == "train"
        assert cell.config["name"] == w["config"]
        cell.reference()
        for m in cell.metrics("per_layer"):
            assert (BENCH / "metrics" / f"{m['name']}.py").exists()


def test_driver_found_by_name(tiny_bench):
    cell = core.Cell("pd.train", bench=tiny_bench)
    drv = cell.driver()
    assert pathlib.Path(drv.__file__) == tiny_bench / "harness" / "train.py"
    assert callable(drv.run)
    cell.traffic = dict(cell.traffic, driver="nonesuch")
    with pytest.raises(FileNotFoundError, match=str(tiny_bench / "harness" / "nonesuch.py")):
        cell.driver()


ECHO = '''"""A driver that only new files bring: squares and sums seeded values on
the device, once per step of the window."""

import time

from harness import core


def run(cell, seed, seconds, tracer, counter, t_process, devs):
    import jax
    import jax.numpy as jnp
    import numpy as np

    x = np.random.default_rng(seed).standard_normal(cell.traffic["n"]).astype(np.float32)
    want = float(np.sum(x.astype(np.float64) ** 2))
    f = jax.jit(lambda v: jnp.sum(v * v))
    f(x).block_until_ready()
    setup_s = time.perf_counter() - t_process
    if tracer.on:
        tracer.add_program(f.lower(x).compile().as_text())
    tracer.start()
    tracer.open_window()
    counter.active = True
    t0, got, steps = time.perf_counter(), [], 0
    while time.perf_counter() - t0 < seconds:
        tracer.tick()
        with core.span("bench.step"):
            got.append(float(f(x)))
        steps += 1
    counter.active = False
    tracer.stop()
    checks = core.Checks(cell.limits)
    checks.add("sum_gap", max(abs(g - want) for g in got) / want)
    return {"kind": "echo", "e2e": {"setup_s": setup_s}, "attempted": steps, "failed": 0,
            "checks": checks, "memory_peak_bytes": core.memory_peak(devs)}
'''


def test_new_driver_takes_only_new_files(tiny_bench):
    """A driver file, a traffic file that names it, the cell's limits and its
    workload entry run a cell: no edit to ``main.py`` or ``core.py``."""
    (tiny_bench / "harness" / "echo.py").write_text(ECHO)
    (tiny_bench / "traffic" / "echo.json").write_text(json.dumps({"driver": "echo", "n": 4096}))
    (tiny_bench / "limits" / "pd.echo.json").write_text(json.dumps({"sum_gap": 1e-5}))
    spec_path = tiny_bench.parent / "BENCHMARK.json"
    spec = json.loads(spec_path.read_text())
    spec["workloads"].append({"name": "pd.echo", "config": "tiny-pd", "traffic": "echo",
                              "chips": 1, "why": "test"})
    spec_path.write_text(json.dumps(spec))
    cell = core.Cell("pd.echo", bench=tiny_bench)
    for trace in (False, True):
        r = main.run_cell(cell, 2**31 + 5, 0.3, trace, time.perf_counter(), require_tpu=False)
        assert r["correct"] and r["attempted"] > 0, r["checks"]
        assert set(r["checks"]) == {"sum_gap"}
        if trace:
            assert r["metrics"] == {} and "breakdown" in r
        else:
            assert set(r["metrics"]) == {"setup_s"}


def test_traced_run_reads_program_layers(tiny_bench, monkeypatch):
    """A traced run turns ``repro.obs`` on over the window alone, hands the
    tracer the step's compiled HLO text, and its last line carries the three
    host-span metrics; the CPU trace has no device plane, so the three
    device-scope readers find nothing and their metrics are left out."""
    from repro import obs

    texts = []
    orig = core.Tracer.add_program
    monkeypatch.setattr(core.Tracer, "add_program",
                        lambda self, text: (texts.append(text), orig(self, text))[1])
    cell = core.Cell("pd.train", bench=tiny_bench)
    r = main.run_cell(cell, 2**31 + 9, 0.5, True, time.perf_counter(), require_tpu=False)
    assert r["correct"], r["checks"]
    assert not obs.enabled()
    assert len(texts) == 1
    assert {"einet.leaf", "em.leaf_stats", "em.mstep"} <= set(
        scopes.hlo_op_scopes(texts[0]).values())
    host = {"train_copy_ms", "train_dispatch_ms", "train_sync_ms"}
    device = {"train_leaf_ms", "train_einsum_ms", "train_mstep_ms"}
    assert host <= set(r["metrics"]) and not device & set(r["metrics"])
    assert all(r["metrics"][m]["value"] > 0 for m in host)
    # train_mfu needs the chip's peak
    assert {"train_input_ms", "train_idle_share"} <= set(r["metrics"])


UNTRACED = """
import collections, json, pathlib, sys, time
sys.path[:0] = {paths!r}
import jax
import conftest
from harness import core, main, scopes
from repro import obs

compiles = collections.Counter()
jax.monitoring.register_event_duration_secs_listener(
    lambda ev, d, **kw: compiles.update([kw.get("fun_name")])
    if ev.endswith("backend_compile_duration") else None)
calls = []
for mod, name in ((obs, "configure"), (jax.profiler, "start_trace"),
                  (core.Tracer, "add_program")):
    orig = getattr(mod, name)
    setattr(mod, name, lambda *a, _o=orig, _n=name, **k: (calls.append(_n), _o(*a, **k))[1])
counters = []
Counter = core.CompileCounter
core.CompileCounter = lambda: counters.append(Counter()) or counters[-1]
bench = conftest.make_tree(pathlib.Path({tmp!r}))
r = main.run_cell(core.Cell("pd.train", bench=bench), 2**31 + 11, 0.5, {trace!r},
                  time.perf_counter(), require_tpu=False)
print(json.dumps({{"compiles": compiles, "calls": calls, "window": counters[0].count,
                  "keys": sorted(r), "device": sorted(r["device"]),
                  "correct": r["correct"]}}))
"""


def test_untraced_run_unchanged(tmp_path):
    """``--trace 0`` does nothing the traced run adds: no profiler, no
    ``repro.obs`` tracing, no scope lookup, no reduction, no compile inside
    the window; and the traced run's scope lookup finds the step already
    compiled, so both runs compile the same programs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    paths = [str(ROOT / "src"), str(BENCH), str(BENCH / "configs"), str(TESTS)]
    out = {}
    for trace in (False, True):
        tmp = tmp_path / str(int(trace))
        code = UNTRACED.format(paths=paths, tmp=str(tmp), trace=trace)
        p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                           env=env, timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        out[trace] = json.loads(p.stdout.strip().splitlines()[-1])
    off, on = out[False], out[True]
    assert off["correct"] and on["correct"]
    assert off["calls"] == [] and off["window"] == 0
    assert "breakdown" not in off["keys"] and "busy_s" not in off["device"]
    assert on["calls"] == ["add_program", "start_trace", "configure", "configure"]
    assert off["compiles"] == on["compiles"]


SHARDED = """
import json, pathlib, sys, time
sys.path[:0] = {paths!r}
import conftest
from harness import core, main, scopes
texts = []
orig = core.Tracer.add_program
core.Tracer.add_program = lambda self, t: (texts.append(t), orig(self, t))[1]
bench = conftest.make_tree(pathlib.Path({tmp!r}))
r = main.run_cell(core.Cell("pd.dp2", bench=bench), 2**31 + 13, 0.5, True,
                  time.perf_counter(), require_tpu=False)
print(json.dumps({{"correct": r["correct"], "programs": len(texts),
                  "scopes": sorted(set(scopes.hlo_op_scopes(texts[0]).values()))}}))
"""


def test_traced_sharded_step_hands_its_program(tmp_path):
    """On two chips the tracer gets the sharded step's program, with the
    statistics' exchange under ``em.allreduce``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    paths = [str(ROOT / "src"), str(BENCH), str(BENCH / "configs"), str(TESTS)]
    p = subprocess.run([sys.executable, "-c", SHARDED.format(paths=paths, tmp=str(tmp_path))],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] and r["programs"] == 1
    assert {"einet.leaf", "em.allreduce", "em.mstep", "plan.gather"} <= set(r["scopes"])
