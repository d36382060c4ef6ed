"""The trace reduction on a small recorded trace: busy union, idle share,
the device clock offset, and idle gaps split by the benchmark's spans."""

import json
import pathlib

import pytest
from harness import scopes, trace

DATA = pathlib.Path(__file__).parent / "data" / "small_trace.json"


@pytest.fixture
def events():
    return [tuple(e) for e in json.loads(DATA.read_text())["events"]]


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_offset_from_launches(events):
    # launches at 1000 and 3000 (host); runs start at 100 and 2150 (device)
    assert trace.host_offset_ns(events, "/device:TPU:0") == 900.0


def test_reduce_busy_idle_and_gaps(events):
    window = trace.spans(events, "bench.window")[0]
    assert window == (0.0, 5000.0)
    r = trace.reduce_trace(events, window)
    # ops (host clock): [1000, 1500) [1600, 1800) [1700, 2000) [3050, 3550) -> 1400 ns
    assert r["chips"][0]["busy_ns"] == pytest.approx(1400.0)
    assert r["busy_s"] == pytest.approx(1400e-9)
    assert r["window_s"] == pytest.approx(5000e-9)
    assert r["idle_share"] == pytest.approx(0.72)
    # the gaps, split by the innermost span: [0, 1000): loader 200-900, step
    # from 950; [1500, 1600): step; [2000, 3050): sleep 2000-2950, then step;
    # [3550, 5000): step to 3650, then none
    gaps = dict(scopes.reduce(events, window)["idle_split"])
    want = {"host": 1600, "bench.loader": 700, "bench.step": 350, "bench.sleep": 950}
    assert gaps == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(1000e-9)
    assert ops["all-reduce.2"] == pytest.approx(300e-9)
    assert r["op_seconds"]["all-reduce.2"] == pytest.approx(300e-9)


def test_two_chips_average(events):
    second = [("/device:TPU:1",) + e[1:] for e in events if e[0] == "/device:TPU:0"]
    window = trace.spans(events, "bench.window")[0]
    r = trace.reduce_trace(list(events) + second, window)
    assert len(r["chips"]) == 2
    assert r["busy_s"] == pytest.approx(1400e-9)


def test_op_name():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(%p)") == "fusion.12"


def test_spans_inside_window(events):
    r = trace.reduce_trace(events, (0.0, 5000.0))
    assert r["spans_inside"] == {"bench.loader": 1, "bench.step": 2, "bench.sleep": 1}
    # the second step (2950-3650) crosses the end of a shorter window
    assert trace.reduce_trace(events, (0.0, 3000.0))["spans_inside"]["bench.step"] == 1
