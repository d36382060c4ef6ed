"""Operation and byte counts of both configurations against a hand count
(see the rules in ``harness/work.py``)."""

import json
import pathlib

import einet_reference as R
import pytest
from harness import work

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def counts(name):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    return work.counts(R.graph_for(cfg), cfg["num_sums"], cfg["num_classes"])


def test_pd_svhn_hand_count():
    # 4 leaf strips of 32x8x3 = 3072 entries at K=40; 7 inner partitions
    # (k_out 40) and 3 at the root (k_out 1); 2 inner regions mix 2
    # partitions, the root mixes 3; 10 regions
    leaf = 4 * 3072 * 40 + 3072 * 40
    part = 7 * 2 * 40 * 40 * 40 + 3 * 2 * 1 * 40 * 40
    mix = 2 * 2 * 2 * 40 + 2 * 3 * 1
    c = counts("einet-pd-svhn")
    assert c["forward_flops"] == leaf + part + mix == 1_520_326
    assert c["train_flops"] == 1_520_326 + 2 * (part + mix) + 5 * 3072 * 40 == 3_946_578
    assert c["bytes"] == 4 * 3072 + 8 * 40 * 10


def test_rat_hand_count():
    # 10 repetitions x 16 leaves of 32 variables = 5120 entries at K=10;
    # 150 partitions, 10 of them under the root (k_out 1); the root mixes 10
    leaf = 4 * 5120 * 10 + 5120 * 10
    part = 140 * 2 * 10 * 10 * 10 + 10 * 2 * 1 * 10 * 10
    mix = 2 * 10 * 1
    c = counts("einet-rat")
    assert c["forward_flops"] == leaf + part + mix == 538_020
    assert c["train_flops"] == 538_020 + 2 * (part + mix) + 5 * 5120 * 10 == 1_358_060
    assert c["bytes"] == 4 * 512 + 8 * 10 * 301


def test_train_mfu_reads_the_traced_window():
    from harness import core

    mfu = core.load_module(CONFIGS.parent / "metrics" / "train_mfu.py")
    run = {"kind": "train", "chips": 1, "batch": 512, "work": {"train_flops": 4e6},
           "peak": {"bf16_flops_per_s": 2e14},
           "trace": {"window_s": 2.0, "spans_inside": {"bench.step": 100}}}
    # 100 steps x 512 rows x 4 MFLOP over 2 s, against 200 TFLOP/s
    assert mfu.read(run) == pytest.approx(100 * 100 * 512 * 4e6 / 2.0 / 2e14)
    run["trace"]["spans_inside"] = {}
    assert mfu.read(run) is None
    assert mfu.read(dict(run, trace=None)) is None
