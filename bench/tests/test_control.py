"""The lower-precision control on the chip: the program with every
contraction at ``HIGH`` (three bf16 passes) in place of the configured
``HIGHEST`` must come out not correct, on three seeds, at the cells' own
sizes.  Needs a TPU; run it on a host with one chip:

    python3 -m pytest bench/tests/test_control.py -q
"""

import json
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
SEEDS = ["3100000003", "3100000019", "3100000021"]


def _tpu():
    p = subprocess.run([sys.executable, "-c", "import jax; print(jax.devices()[0].platform)"],
                       capture_output=True, text=True, timeout=300)
    return p.stdout.strip() == "tpu"


@pytest.mark.parametrize("cell", ["pd_svhn.em_b512", "rat.em_b2048"])
def test_control_is_not_correct(cell):
    if not _tpu():
        pytest.skip("the control is read on the chip; this host has no TPU")
    p = subprocess.run([sys.executable, str(BENCH / "tools" / "readings.py"), "precision_high",
                        cell, "5"] + SEEDS, capture_output=True, text=True,
                       cwd=str(BENCH.parent), timeout=1800)
    assert p.returncode == 0, p.stderr[-3000:]
    rows = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    assert len(rows) == len(SEEDS)
    assert not any(r["correct"] for r in rows), rows
