"""Whole runs of tiny cells on the CPU: the last line's schema, the chip
check, and ``correct`` coming out false under each fault a cell can have."""

import json
import os
import pathlib
import subprocess
import sys
import time

import pytest
from harness import core, main

TESTS = pathlib.Path(__file__).resolve().parent
BENCH = TESTS.parent
ROOT = BENCH.parent

SCRIPT = """
import json, pathlib, sys, time
sys.path[:0] = {paths!r}
import conftest, plants
from harness import core, main
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
