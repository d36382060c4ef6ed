"""Benchmark entry point: run one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload pd_svhn.em_b512 --seed 7 --seconds 10 --trace 0

Prints one JSON line last on standard output; exits non-zero, with no
result, where JAX finds no TPU or fewer chips than the cell needs.
"""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "bench"))
sys.path.insert(0, os.path.join(_ROOT, "src"))

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(T_PROCESS))
