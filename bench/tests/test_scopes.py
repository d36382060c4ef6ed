"""The reduction of a trace that holds the program's spans and named scopes
(``harness/scopes.py``), on a small recorded trace: idle gaps split by the
innermost span, device time per scope, and the per-step layer times; and
the same reduction of a cell run on the CPU with ``repro.obs`` tracing on."""

import json
import pathlib
import sys
import time

import pytest
from harness import core, scopes, trace

DATA = pathlib.Path(__file__).parent / "data"
METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def load(name):
    return [tuple(e) for e in json.loads((DATA / name).read_text())["events"]]


@pytest.fixture
def reduced():
    events = load("scoped_trace.json")
    return scopes.reduce(events, trace.spans(events, "bench.window")[0])


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/em.estep/einet.leaf/jit(clip)", "einet.leaf"),
    ("jit(step)/em.estep/em.leaf_stats/bpk,bpt->pkt", "em.leaf_stats"),
    ("jit(step)/em.estep/jvp(plan.gather)/lkij,bli,blj->blk", "plan.gather"),
    ("jit(step)/em.estep/transpose(jvp(plan.fused))", "plan.fused"),
    ("jit(step)/em.estep/transpose(em.estep)/jvp(plan.layer)", "plan.layer"),
    ("jit(step)/em.mstep/jit(clip)", "em.mstep"),
    ("jit(step)/em.estep/jvp()", "em.estep"),
    ("jit(step)/em.mstep/add;jit(step)/em.estep/einet.leaf/add", "em.mstep"),
    ("reduce_sum", ""),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


HLO = """
ENTRY %main {
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/em.estep/jvp(plan.gather)/add" source_file="einet.py"}
  %copy.230 = f32[8]{0:T(128)} copy(f32[8]{0} %fusion.3), metadata={op_name="jit(step)/em.estep/einet.leaf/transpose"}
  %copy-done.4 = f32[8]{0} copy-done(%copy-start.4)
  ROOT %tuple.1 = (f32[8]{0}) tuple(%copy.230), metadata={op_name="jit(step)/em.mstep"}
}
"""


def test_hlo_op_scopes():
    assert scopes.hlo_op_scopes(HLO) == {"fusion.3": "plan.gather",
                                         "copy.230": "einet.leaf",
                                         "tuple.1": "em.mstep"}


def test_idle_split_by_innermost_span(reduced):
    # ops (host clock, offset 800): [1500, 3400) and [5100, 7000); window 10000
    split = dict(reduced["idle_split"])
    want = {"host": 3500, "train.copy": 800, "train.record": 800,
            "bench.loader": 500, "train.dispatch": 200, "train.sync": 200,
            "bench.step": 200}
    assert split == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(split.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    assert scopes.split_gap([], 0, 10) == {"host": 10}


def test_device_time_per_scope(reduced):
    want = {"einet.leaf": 560, "em.leaf_stats": 400, "plan.gather": 1600,
            "plan.layer": 600, "em.mstep": 400, "em.estep": 200}
    assert reduced["scope_seconds"] == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert reduced["op_scopes"]["copy.5"] == "plan.layer"
    assert reduced["steps"] == 2


def test_layer_ms(reduced):
    # per step, in ms: two bench.step spans lie inside the window
    assert scopes.layer_ms(reduced) == pytest.approx({
        "train_copy_ms": 4e-4, "train_dispatch_ms": 2e-4, "train_sync_ms": 1.9e-3,
        "train_leaf_ms": 4.8e-4, "train_einsum_ms": 1.1e-3, "train_mstep_ms": 2e-4})


NEW_METRICS = ("train_copy_ms", "train_dispatch_ms", "train_sync_ms",
               "train_leaf_ms", "train_einsum_ms", "train_mstep_ms")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_layer_readers(reduced, name):
    read = core.load_module(METRICS / f"{name}.py").read
    assert read({"kind": "train", "trace": reduced}) == scopes.layer_ms(reduced)[name]
    assert read({"kind": "train", "trace": None}) is None
    events = load("small_trace.json")
    bare = scopes.reduce(events, trace.spans(events, "bench.window")[0])
    assert read({"kind": "train", "trace": bare}) is None


def test_loop_op_counted_once(reduced):
    """The loop op ``while.3`` holds its body's two trips of ``fusion.11``:
    the leaf layer is the body's ops and the other step's ``fusion.1``
    alone, while the accepted reduction still lists the loop op."""
    body = 2 * 130 + 300
    assert reduced["scope_seconds"]["einet.leaf"] == pytest.approx(body * 1e-9)
    ops = reduced["op_seconds"]
    assert ops["while.3"] == pytest.approx(300e-9)
    assert reduced["scope_seconds"]["einet.leaf"] == pytest.approx(
        sum(v for op, v in ops.items()
            if reduced["op_scopes"][op] == "einet.leaf" and op != "while.3"))


@pytest.mark.parametrize("ops,held", [
    ([(0, 10, "a"), (1, 5, "a"), (5, 9, "a")], {0}),
    ([(0, 10, "a"), (1, 5, "b")], set()),
    ([(0, 10, "a"), (0, 10, "a")], set()),
    ([(0, 5, "a"), (5, 9, "a")], set()),
    ([(0, 10, "a"), (2, 8, "a"), (3, 4, "a")], {0, 1}),
])
def test_holders(ops, held):
    assert scopes.holders(ops) == held


def test_accepted_reduction_unchanged(reduced):
    """The accepted reduction reads the new flattening as it reads its own:
    program spans and scopes move none of its numbers, and the accepted
    per-layer metrics read the same from the benchmark's reduction
    (:func:`harness.scopes.reduce`) as from the accepted one."""
    events = load("scoped_trace.json")
    window = trace.spans(events, "bench.window")[0]
    plain = [e[:5] for e in events if not scopes.is_program_span(e[2])]
    accepted = trace.reduce_trace(plain, window)
    assert trace.reduce_trace(events, window) == accepted
    assert {k: v for k, v in reduced.items() if k in accepted} == accepted
    run = {"kind": "train", "batch": 512, "work": {"train_flops": 3.95e6},
           "input_ms_per_step": 0.8, "chips": 1, "peak": {"bf16_flops_per_s": 1.97e14}}
    for name in ("train_input_ms", "train_mfu", "train_idle_share"):
        read = core.load_module(METRICS / f"{name}.py").read
        assert read(dict(run, trace=reduced)) == read(dict(run, trace=accepted)) is not None


def test_nothing_to_read_without_spans_or_scopes():
    events = load("small_trace.json")
    r = scopes.reduce(events, trace.spans(events, "bench.window")[0])
    assert r["span_seconds"] == {} and set(r["scope_seconds"]) == {""}
    assert scopes.layer_ms(r) == {}
    assert scopes.layer_ms(None) == {}


def test_probe_reads_program_spans_on_cpu(tiny_bench):
    """A tiny cell run on the CPU with the profiler and ``repro.obs`` on:
    the program's four spans land in the trace once per step; with
    ``repro.obs`` off none do.  (The CPU trace has no device plane, so no
    device scopes.)"""
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "tools"))
    import scope_probe

    cell = core.Cell("pd.train", bench=tiny_bench)
    for obs_on in (True, False):
        r = scope_probe.probe(cell, 2**31 + 77, 0.5, obs_on, require_tpu=False,
                              t_process=time.perf_counter())
        assert r["correct"]
        assert r["accepted"] == r["accepted_on_new_flattening"]
        if obs_on:
            assert {"train.copy", "train.dispatch", "train.sync",
                    "train.record"} <= set(r["span_seconds"])
            assert set(r["layers_ms"]) == {"train_copy_ms", "train_dispatch_ms",
                                           "train_sync_ms"}
        else:
            assert r["span_seconds"] == {} and r["layers_ms"] == {}
