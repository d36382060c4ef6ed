"""Throughput / latency measurement: engine vs the one-call-at-a-time path.

One routine, shared by ``repro.launch.serve --arch einet_*`` and
``benchmarks/bench_serve.py``, so the driver's printed numbers and the
``BENCH_serve.json`` perf trajectory come from the same measurement:

  * warm-up (program compilation) is timed separately from steady state --
    compile cost is paid once per (kind, bucket), never per request;
  * steady state reruns the identical stream against the warm program cache;
  * latency is PER REQUEST, enqueue -> complete, read from the engine's
    ``serve.request.seconds`` histograms (the whole-stream wall clock hid
    the per-kind distribution -- a slow sampling request was invisible
    behind 63 fast LLs): steady-state-only percentiles come from marking
    the histogram counts before the timed passes and diffing after;
  * two baselines, both one-call-at-a-time: ``legacy_call`` is per-request
    serving with the pre-engine sampling bug intact (jitted LLs, *unjitted*
    sampling -- serve.py:80), the "current path" the >= 5x bar refers to;
    ``direct_call`` is the stronger fully-jitted per-request path, so the
    report also isolates pure batching/dispatch amortization from the jit
    fix;
  * every engine result is checked against the direct path (parity), in
    absolute terms and relative to the value's magnitude: the engine's
    bucketed program and the batch-1 direct program may associate their
    float32 reductions differently (they do on TPU), so only the relative
    difference is a bound that holds at every |LL|.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro import obs
from repro.obs import METRICS, percentile_from_counts
from repro.serve.engine import Request, ServeEngine
from repro.serve.workload import direct_call, legacy_call


def _program_cache_counts() -> Dict[str, int]:
    """Process-wide program-cache counters (diff two snapshots to scope
    them to one benchmark): engine-dict fast-path hits/misses plus the
    shared registry's AOT compile count (a registry miss IS a compile)."""
    return {
        "hits": int(sum(
            m.value for _, m in METRICS.find("serve.program_cache.hits"))),
        "misses": int(sum(
            m.value for _, m in METRICS.find("serve.program_cache.misses"))),
        "registry_compiles": int(sum(
            m.value
            for _, m in METRICS.find("compile.cache.misses", kind="aot"))),
    }


def run_benchmark(
    model,
    params,
    requests: Sequence[Request],
    max_batch: int = 0,
    reps: int = 3,
    rules: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """``max_batch=0`` derives the micro-batch cap from the stream size
    (min(32, n)) -- the one defaulting rule both CLIs share."""
    n = len(requests)
    if n == 0:
        raise ValueError("run_benchmark needs at least one request")
    reps = max(1, int(reps))
    max_batch = max_batch or max(1, min(32, n))
    engine = ServeEngine(model, params, max_batch=max_batch, rules=rules)
    kinds = sorted({r.kind for r in requests})
    cache0 = _program_cache_counts()

    # -- warm-up pass: compiles the program cache on demand
    with obs.timed("serve.bench.warmup") as t_warm:
        results = engine.run(requests)

    warm_steps = engine.stats["steps"]
    warm_padded = engine.stats["padded_rows"]

    # -- steady state: identical stream, warm cache.  Mark the per-request
    # latency histograms here so the percentiles below cover ONLY the timed
    # passes (warm-up latencies include compiles; they must not pollute)
    marks: Dict[str, List[int]] = {
        k: METRICS.sum_histogram("serve.request.seconds", kind=k)
        for k in kinds
    }
    with obs.timed("serve.bench.steady", reps=reps) as t_st:
        for _ in range(reps):
            results = engine.run(requests)
    t_steady = t_st.seconds / reps
    latency_ms: Dict[str, Dict[str, float]] = {}
    for k in kinds:
        after = METRICS.sum_histogram("serve.request.seconds", kind=k)
        delta = [a - b for a, b in zip(after, marks[k])]
        latency_ms[k] = {
            f"p{q}": round(percentile_from_counts(delta, q) * 1e3, 4)
            for q in (50, 95, 99)
        }
    # per-stream scheduling stats (engine.stats accumulate across passes)
    steps_per_pass = (engine.stats["steps"] - warm_steps) // reps
    padded_per_pass = (engine.stats["padded_rows"] - warm_padded) // reps
    cache1 = _program_cache_counts()

    # -- strong baseline: fully-jitted one-call-at-a-time (warmed the same way)
    call = direct_call(model, params)
    with obs.timed("serve.bench.direct_warmup") as t_dw:
        direct = {r.req_id: np.asarray(call(r)) for r in requests}
    with obs.timed("serve.bench.direct") as t_d:
        direct = {r.req_id: np.asarray(call(r)) for r in requests}

    # -- acceptance baseline: the pre-engine path (unjitted sampling).
    # One warm pass primes the jitted LL programs + eager op caches so the
    # timed pass is its steady state too.
    legacy = legacy_call(model, params)
    for r in requests:
        np.asarray(legacy(r))
    with obs.timed("serve.bench.legacy") as t_l:
        for r in requests:
            np.asarray(legacy(r))
    t_legacy = t_l.seconds

    diffs = {
        i: np.abs(np.asarray(results[i].value, np.float64) - direct[i])
        for i in direct
    }
    parity = max(float(np.max(d)) for d in diffs.values())
    parity_rel = max(
        float(np.max(d / (1.0 + np.abs(direct[i]))))
        for i, d in diffs.items()
    )
    return {
        "num_requests": n,
        "kinds": kinds,
        "max_batch": max_batch,
        "buckets": list(engine.buckets),
        "reps": reps,
        "warmup_s": t_warm.seconds,
        "compile_s": engine.stats["compile_s"],
        "direct_warmup_s": t_dw.seconds,
        "steady_s": t_steady,
        "engine_qps": n / t_steady,
        "latency_ms": latency_ms,
        "program_cache": {k: cache1[k] - cache0[k] for k in cache1},
        "direct_s": t_d.seconds,
        "direct_qps": n / t_d.seconds,
        "legacy_s": t_legacy,
        "legacy_qps": n / t_legacy,
        "speedup": t_legacy / t_steady,
        "speedup_vs_jitted": t_d.seconds / t_steady,
        "programs": engine.num_programs,
        "compiles": engine.stats["compiles"],
        "scheduler_steps": steps_per_pass,
        "padded_rows": padded_per_pass,
        # high-watermark, not last-write: the queue drains before the report
        # is assembled, so the plain gauge value always reads ~0 here
        "queue_depth_max": METRICS.gauge("serve.queue.depth").max,
        "parity_max_abs_diff": parity,
        "parity_max_rel_diff": parity_rel,
    }


def format_report(r: Dict[str, Any]) -> str:
    lines = [
        f"batched exact-inference engine: {r['num_requests']} requests, "
        f"kinds={','.join(r['kinds'])}, max_batch={r['max_batch']}",
        f"warm-up   : engine {r['warmup_s']*1e3:.0f} ms "
        f"({r['programs']} programs, compile {r['compile_s']*1e3:.0f} ms); "
        f"direct path {r['direct_warmup_s']*1e3:.0f} ms",
        f"steady    : engine {r['steady_s']*1e3:.1f} ms "
        f"({r['engine_qps']:.0f} req/s)",
    ]
    for kind, lm in sorted(r.get("latency_ms", {}).items()):
        lines.append(
            f"latency   : {kind:<24s} p50 {lm['p50']:8.3f} ms   "
            f"p95 {lm['p95']:8.3f} ms   p99 {lm['p99']:8.3f} ms"
        )
    pc = r.get("program_cache")
    if pc:
        lines.append(
            f"prog cache: {pc['hits']} hits / {pc['misses']} misses "
            f"({pc['registry_compiles']} registry compiles)"
        )
    lines += [
        f"baselines : current one-call-at-a-time (unjitted sampling) "
        f"{r['legacy_s']*1e3:.1f} ms ({r['legacy_qps']:.0f} req/s) -> "
        f"{r['speedup']:.1f}x; fully-jitted per-request "
        f"{r['direct_s']*1e3:.1f} ms ({r['direct_qps']:.0f} req/s) -> "
        f"{r['speedup_vs_jitted']:.1f}x",
        f"parity    : max|engine - direct| = {r['parity_max_abs_diff']:.2e} "
        f"(relative to 1 + |direct|: {r['parity_max_rel_diff']:.2e})",
        f"programs  : {r['programs']} cached / {r['compiles']} compiles "
        f"({r['scheduler_steps']} scheduler steps, "
        f"{r['padded_rows']} padded filler rows per stream)",
    ]
    return "\n".join(lines)
