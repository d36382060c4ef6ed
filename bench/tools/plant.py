"""One benchmark run with a fault or the lower-precision control planted
in the program (see ``bench/tests/plants.py``).

    python3 bench/tools/plant.py PLANT --workload CELL --seed N --seconds S --trace 0
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.join(_ROOT, "bench"),
                os.path.join(_ROOT, "bench", "tests")]

import plants  # noqa: E402
from harness.main import main  # noqa: E402

if __name__ == "__main__":
    plants.PLANTS[sys.argv[1]]()
    sys.exit(main(T_PROCESS, sys.argv[2:]))
