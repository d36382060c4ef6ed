"""The numbers ``correct`` compares, read on many seeds in one process, with
the program sound or with a fault or the control planted
(``bench/tests/plants.py``): the readings the limits are set from.

    python3 bench/tools/readings.py none pd_svhn.em_b512 0.5 SEED [SEED ...]
    python3 bench/tools/readings.py precision_high pd_svhn.em_b512 0.5 SEED ...

The third argument is the window in seconds; training's readings need
none, so a short one does.
"""
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.join(_ROOT, "bench"),
                os.path.join(_ROOT, "bench", "tests")]

import plants  # noqa: E402
from harness import core, main  # noqa: E402


def run(plant, workload, seconds, seeds):
    if plant != "none":
        plants.PLANTS[plant]()
    cell = core.Cell(workload)
    for seed in seeds:
        t0 = time.perf_counter()
        r = main.run_cell(cell, int(seed), float(seconds), False, t0)
        print(json.dumps({"plant": plant, "workload": workload, "seed": int(seed),
                          "correct": r["correct"], "failed": r["failed"],
                          "checks": {k: v["value"] for k, v in r["checks"].items()},
                          "wall_s": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    run(sys.argv[1], sys.argv[2], sys.argv[3], sys.argv[4:])
