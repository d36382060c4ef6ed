"""Observability tests: span nesting + Chrome-trace export schema, log-bucket
histogram percentiles against numpy, steady-state baseline subtraction,
counter thread-safety, the disabled path costing nothing AND changing
nothing (bitwise-identical serve results traced vs untraced), spans landing
in the ``jax.profiler`` trace, and the single-source compile-event
accounting shared with ``analysis.sentry``."""

import json
import pathlib
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax

from repro import obs
from repro.obs import METRICS
from repro.obs.check import validate_events
from repro.obs.metrics import NUM_BUCKETS, percentile_from_counts


@pytest.fixture(autouse=True)
def obs_clean_slate():
    """Every test starts with tracing off and an empty buffer; the global
    METRICS registry is process-wide, so tests read *deltas*, not totals."""
    obs.configure(trace=False)
    obs.reset()
    yield
    obs.configure(trace=False)
    obs.reset()


# ------------------------------------------------------------------- tracing
def test_span_nesting_and_export_schema(tmp_path):
    obs.configure(trace=True)
    with obs.span("outer.op", kind="a"):
        with obs.span("inner.op", idx=0):
            pass
        with obs.span("inner.op", idx=1):
            pass
    obs.event("marker.point", note="x")
    assert obs.num_events() == 4

    path = tmp_path / "trace.json"
    out = obs.export_trace(str(path))
    assert out == str(path)
    doc = json.loads(path.read_text())  # round-trips through real JSON
    assert validate_events(doc, ("outer.", "inner.")) == []
    assert doc["displayTimeUnit"] == "ms"

    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    outer = evs["outer.op"]
    inners = [e for e in doc["traceEvents"] if e["name"] == "inner.op"]
    assert len(inners) == 2 and [e["args"]["idx"] for e in inners] == [0, 1]
    # Perfetto reconstructs nesting from ts/dur containment: both inner
    # spans must lie inside the outer span's [ts, ts + dur] window
    for e in inners:
        assert outer["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"kind": "a"}


def test_validate_events_catches_bad_traces():
    assert validate_events([]) != []
    assert validate_events({"traceEvents": "nope"}) != []
    bad_dur = {"traceEvents": [
        {"ph": "X", "ts": 0.0, "name": "a", "args": {}}]}  # missing dur
    assert any("dur" in p for p in validate_events(bad_dur))
    ok = {"traceEvents": [
        {"ph": "X", "ts": 0.0, "dur": 1.0, "name": "serve.step", "args": {}}]}
    assert validate_events(ok, ("serve.",)) == []
    assert any("required subsystem" in p
               for p in validate_events(ok, ("train.",)))


def test_disabled_span_is_shared_noop_and_buffers_nothing():
    assert not obs.enabled()
    s1, s2 = obs.span("a"), obs.span("b", k=1)
    assert s1 is s2  # the no-op singleton: no per-call allocation
    with s1:
        pass
    obs.event("nope")
    assert obs.num_events() == 0


def _profiled_host_events(tmp_path, body):
    """Run ``body`` under a ``jax.profiler`` trace; the host plane's events
    as {name: [stats dict, ...]}."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    [path] = list(tmp_path.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    out = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.duration_ns, dict(e.stats)))
    return out


def _spans_and_timed():
    with obs.span("unit.outer", step=3, kind="a"):
        with obs.timed("unit.timed", idx=1):
            sum(range(1000))


@pytest.mark.parametrize("on", [True, False], ids=["enabled", "disabled"])
def test_spans_land_in_profiler_trace(tmp_path, on):
    """With tracing on, span and timed open a profiler annotation of the
    same name carrying their args, nested as in the code; with it off,
    nothing lands (and span stays the null singleton)."""
    obs.configure(trace=on)
    events = _profiled_host_events(tmp_path, _spans_and_timed)
    if not on:
        assert "unit.outer" not in events and "unit.timed" not in events
        assert obs.span("unit.outer") is obs.span("unit.other")
        return
    [(o_start, o_dur, o_stats)] = events["unit.outer"]
    [(t_start, t_dur, t_stats)] = events["unit.timed"]
    assert o_stats == {"step": 3, "kind": "a"}
    assert t_stats == {"idx": 1}
    assert o_start <= t_start and t_start + t_dur <= o_start + o_dur
    # the Chrome buffer is the same switch's other sink
    assert [e["name"] for e in obs.trace_events()] == ["unit.timed",
                                                       "unit.outer"]


def test_obs_imports_without_jax():
    """``repro.obs`` stays importable before (and without) jax: jax is
    imported only at the first span opened with tracing on."""
    code = ("import sys; import repro.obs as o; "
            "assert 'jax' not in sys.modules; "
            "o.span('a').__enter__(); assert 'jax' not in sys.modules; "
            "o.configure(trace=True); "
            "o.span('a').__enter__(); assert 'jax' in sys.modules")
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={"PYTHONPATH": src,
                                       "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr


def test_timed_measures_even_when_disabled_and_feeds_metric():
    before = METRICS.sum_histogram("test.obs.seconds")
    with obs.timed("test.obs", metric="test.obs.seconds", tag="t") as t:
        x = sum(range(1000))
    assert x == 499500 and t.seconds > 0.0
    assert obs.num_events() == 0  # tracing off: no event, but measured
    delta = [a - b for a, b in
             zip(METRICS.sum_histogram("test.obs.seconds"), before)]
    assert sum(delta) == 1
    labels = [d for d, _ in METRICS.find("test.obs.seconds", tag="t")]
    assert labels and labels[0] == {"tag": "t"}


# ------------------------------------------------------------------- metrics
def test_histogram_percentile_matches_numpy():
    rng = np.random.RandomState(0)
    samples = rng.lognormal(mean=-6.0, sigma=1.5, size=5000)
    h = METRICS.histogram("test.obs.hist.seconds")
    base = h.counts()
    for v in samples:
        h.record(float(v))
    counts = [a - b for a, b in zip(h.counts(), base)]
    for q in (50, 90, 95, 99):
        got = percentile_from_counts(counts, q)
        want = float(np.percentile(samples, q))
        # log buckets at 24/decade -> half-bucket relative error ~5%
        assert got == pytest.approx(want, rel=0.08), f"p{q}"


def test_percentile_baseline_reads_only_the_interval():
    h = METRICS.histogram("test.obs.base.seconds")
    for _ in range(50):
        h.record(1e-3)  # "warm-up": slow
    mark = h.counts()
    for _ in range(50):
        h.record(1e-5)  # steady state: fast
    p95_all = h.percentile(95)
    p95_steady = h.percentile(95, baseline=mark)
    assert p95_steady == pytest.approx(1e-5, rel=0.08)
    assert p95_all > p95_steady * 5  # mixed window drags the tail upward


def test_counter_thread_safety_exact():
    c = METRICS.counter("test.obs.threads.count")
    start = c.value
    n_threads, per_thread = 8, 5000

    def work():
        for _ in range(per_thread):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value - start == n_threads * per_thread  # no lost updates


def test_snapshot_is_plain_json(tmp_path):
    METRICS.counter("test.obs.snap.count", kind="a").inc(3)
    METRICS.gauge("test.obs.snap.depth").set(7)
    METRICS.histogram("test.obs.snap.seconds").record(0.01)
    snap = METRICS.snapshot()
    text = json.dumps(snap)  # must be JSON-able as-is (BENCH files)
    back = json.loads(text)
    assert back["test.obs.snap.count{kind=a}"] == 3
    # gauges snapshot value + high-watermark (bursty gauges like queue depth
    # read ~0 at end-of-run without the max)
    assert back["test.obs.snap.depth"]["value"] == 7.0
    assert back["test.obs.snap.depth"]["max"] == 7.0
    h = back["test.obs.snap.seconds"]
    assert h["count"] >= 1 and h["p50"] > 0


def test_registry_rejects_type_confusion():
    METRICS.counter("test.obs.typed")
    with pytest.raises(TypeError, match="already registered"):
        METRICS.gauge("test.obs.typed")


# ------------------------------------------- compile events (single source)
def test_compile_events_single_source_with_listener():
    """ProgramRegistry is the only emitter: one miss + one hit produce
    exactly one compile event and one cache hit, and the subscribed
    listener (the sentry mechanism) sees exactly the one compile."""
    from repro.compile import ProgramRegistry

    miss0 = METRICS.value("compile.cache.misses", kind="aot")
    hit0 = METRICS.value("compile.cache.hits", kind="aot")
    seen = []
    token = obs.on_compile(seen.append)
    try:
        reg = ProgramRegistry()

        class Anchor:  # plain object() is not weakref-able
            pass

        anchor = Anchor()

        def f(a):
            return a * 2.0

        args = (np.ones((2,), np.float32),)
        p1 = reg.aot(anchor, ("k", 2), f, args)
        p2 = reg.aot(anchor, ("k", 2), f, args)  # cache hit
        assert p1 is p2
        assert reg.stats["compiles"] == 1 and reg.stats["hits"] == 1
    finally:
        obs.remove_compile_listener(token)
    assert METRICS.value("compile.cache.misses", kind="aot") - miss0 == 1
    assert METRICS.value("compile.cache.hits", kind="aot") - hit0 == 1
    assert len(seen) == 1
    assert seen[0]["kind"] == "aot" and "('k', 2)" in seen[0]["key"]
    assert seen[0]["seconds"] >= 0.0
    # removed listener hears nothing further
    obs.compile_event("aot", ("k", 3), 0.0)
    assert len(seen) == 1


# --------------------------------------------------- disabled-mode identity
def test_serve_results_bitwise_identical_traced_vs_untraced():
    """Tracing must be observational only: the same request stream through
    fresh engines, traced and untraced, yields bitwise-identical bytes."""
    from repro.core import EiNet, Normal, random_binary_trees
    from repro.serve import ServeEngine, mixed_requests

    g = random_binary_trees(8, 2, 2, seed=0)
    net = EiNet(g, num_sums=3, exponential_family=Normal())
    params = net.init(jax.random.PRNGKey(0))
    reqs = mixed_requests(net.num_vars, 12, seed=0)

    obs.configure(trace=False)
    plain = ServeEngine(net, params, max_batch=4).run(reqs)

    obs.configure(trace=True)
    traced = ServeEngine(net, params, max_batch=4).run(reqs)
    assert obs.num_events() > 0  # tracing actually collected spans

    assert sorted(plain) == sorted(traced)
    for rid in plain:
        a, b = plain[rid], traced[rid]
        assert a.kind == b.kind
        va, vb = np.asarray(a.value), np.asarray(b.value)
        assert va.dtype == vb.dtype and va.shape == vb.shape
        assert va.tobytes() == vb.tobytes()  # bitwise, not approx


def test_summary_rolls_up_serve_and_plan():
    req0 = sum(METRICS.sum_histogram("serve.request.seconds"))
    METRICS.histogram("serve.request.seconds",
                      kind="joint_ll", bucket=4).record(2e-3)
    s = obs.summary()
    assert s["serve_requests"] >= req0 + 1
    assert set(s["serve_latency_ms"]) == {"p50", "p95", "p99"}
    assert isinstance(obs.format_summary(), str)


# --------------------------------------------------------- buffer mechanics
def test_buffer_cap_counts_dropped(monkeypatch):
    from repro.obs import trace as trace_mod

    monkeypatch.setattr(trace_mod, "_MAX_EVENTS", 3)
    obs.configure(trace=True)
    for i in range(5):
        obs.event("e", i=i)
    assert obs.num_events() == 3
    assert trace_mod._STATE.dropped == 2  # counted, not silently lost
    obs.reset()
    assert obs.num_events() == 0 and trace_mod._STATE.dropped == 0
