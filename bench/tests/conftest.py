"""Self-checks of the benchmark, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

``tiny_bench`` copies the benchmark's code into a temporary tree with small
configurations, traffic and cells, so a whole run can be driven without a
chip (``require_tpu=False``).
"""

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH), str(BENCH / "configs")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_PD = {"name": "tiny-pd", "reference": "einet_reference", "structure": "pd",
           "height": 8, "width": 8, "num_channels": 3, "delta": 2, "pd_axes": ["w"],
           "num_sums": 4, "num_classes": 1, "exponential_family": "normal",
           "min_var": 1e-6, "max_var": 10.0, "batch_size": 64}
TINY_RAT = {"name": "tiny-rat", "reference": "einet_reference", "structure": "rat",
            "num_vars": 32, "depth": 2, "num_repetitions": 3, "num_sums": 4,
            "num_classes": 1, "exponential_family": "normal", "min_var": 1e-6,
            "max_var": 10.0, "batch_size": 64}
TRAIN = {"driver": "train", "batch": 64, "rows": 512, "step_size": 0.5,
         "laplace_alpha": 1e-4, "stat_floor": 1e-12}
TRAIN_LIMITS = {"ll_gap": 1e-5, "mstep_gap": 1e-3, "change_gap": 1e-3}


def make_tree(tmp: pathlib.Path) -> pathlib.Path:
    """A benchmark tree with the tiny cells ``pd.train``, ``rat.train``,
    and ``pd.dp2`` (two devices); returns its ``bench`` dir."""
    bench = tmp / "bench"
    for d in ("harness", "metrics"):
        shutil.copytree(BENCH / d, bench / d)
    (bench / "configs").mkdir(parents=True)
    shutil.copy(BENCH / "configs" / "einet_reference.py", bench / "configs")
    shutil.copy(BENCH / "peaks.json", bench)
    for name, cfg in (("tiny-pd", TINY_PD), ("tiny-rat", TINY_RAT)):
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (bench / "traffic").mkdir()
    (bench / "traffic" / "train.json").write_text(json.dumps(TRAIN))
    (bench / "limits").mkdir()
    cells = [("pd.train", "tiny-pd", "train", 1), ("rat.train", "tiny-rat", "train", 1),
             ("pd.dp2", "tiny-pd", "train", 2)]
    for name, _, _, _ in cells:
        (bench / "limits" / f"{name}.json").write_text(json.dumps(TRAIN_LIMITS))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": n, "source": "test", "file": f"bench/configs/{n}.json",
                        "reduced": [], "why": "test"} for n in ("tiny-pd", "tiny-rat")]
    spec["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": k, "why": "test"}
                         for n, c, t, k in cells]
    train_cells = ["pd.train", "rat.train", "pd.dp2"]
    for section in ("end_to_end", "per_layer"):
        for m in spec[section]:
            if "workloads" in m:
                m["workloads"] = train_cells
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


@pytest.fixture
def tiny_bench(tmp_path):
    return make_tree(tmp_path)
