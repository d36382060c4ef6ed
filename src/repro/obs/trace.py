"""Tracing spans: nestable context managers -> Chrome/Perfetto trace JSON
and the ``jax.profiler`` trace.

The tracing half of ``repro.obs``: ``span("serve.execute", kind=..., ...)``
wraps a region of host code and, when tracing is enabled, writes it to two
sinks:

  * one Chrome ``trace_event`` *complete* event (``ph: "X"`` with
    ``ts``/``dur`` in microseconds) in a thread-safe in-process buffer that
    ``export_trace(path)`` writes as a JSON file loadable by
    ``chrome://tracing`` / ui.perfetto.dev.  Nesting needs no bookkeeping --
    the viewer reconstructs the stack from ``ts``/``dur`` containment per
    thread;
  * one ``jax.profiler.TraceAnnotation`` of the same name, the span's args
    as its metadata.  While a ``jax.profiler`` trace runs, the span lands
    on the profiler's host plane, on the clock of the program launches, so
    a device trace can be read against the program's own spans.  Outside a
    profiler trace the annotation records nothing.

Enable switches (the disabled path must cost ~nothing -- ``span()`` returns
a shared no-op singleton, one attribute read + one ``if``):

  * ``REPRO_TRACE`` env var: any truthy value enables collection; a value
    that looks like a path (contains ``/`` or ends in ``.json``) also
    registers an atexit export to that path.
  * ``configure(trace=True/False)``: programmatic override (the launch
    CLIs' ``--trace out.json`` flag).

Two flavours of timed region:

  * :func:`span` -- trace-only; a no-op when tracing is off.  For hot paths
    where even a clock read per call would be waste.
  * :func:`timed` -- ALWAYS measures (exposes ``.seconds`` after exit) and
    optionally records into a metrics histogram; emits the trace event only
    when tracing is on.  This is the migration target for the repo's former
    ad-hoc ``time.perf_counter()`` bookkeeping.

jax-free and numpy-free by design: ``repro.obs`` must be importable from
every layer (including ``repro.compile`` before jax loads) without cycles.
jax is imported at the first span opened with tracing on.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

# trace-time clock origin: event ts are microseconds since process start
_T0_NS = time.perf_counter_ns()

# buffer hard cap -- a runaway instrumented loop must not eat the host;
# events past the cap are counted, not stored
_MAX_EVENTS = 1_000_000


def _env_truthy(value: str) -> bool:
    return value.strip().lower() not in ("", "0", "false", "off", "no")


def _env_path(value: str) -> Optional[str]:
    v = value.strip()
    if "/" in v or v.endswith(".json"):
        return v
    return None


class _TraceState:
    __slots__ = ("enabled", "annotation", "lock", "events", "dropped",
                 "export_path", "_atexit_armed")

    def __init__(self):
        env = os.environ.get("REPRO_TRACE", "")
        self.enabled = _env_truthy(env)
        self.annotation = None  # jax.profiler.TraceAnnotation, imported lazily
        self.lock = threading.Lock()
        self.events: List[Dict[str, Any]] = []
        self.dropped = 0
        self.export_path = _env_path(env)
        self._atexit_armed = False
        if self.export_path:
            self._arm_atexit()

    def _arm_atexit(self):
        if not self._atexit_armed:
            self._atexit_armed = True
            atexit.register(_atexit_export)


_STATE = _TraceState()


def _atexit_export():
    if _STATE.export_path and _STATE.events:
        export_trace(_STATE.export_path)


def configure(trace: Optional[bool] = None,
              export_path: Optional[str] = None) -> None:
    """Process-wide switch: ``configure(trace=True)`` starts collecting,
    ``configure(trace=False)`` stops (buffered events are kept -- call
    :func:`reset` to drop them).  ``export_path`` arms an atexit export."""
    if trace is not None:
        _STATE.enabled = bool(trace)
    if export_path is not None:
        _STATE.export_path = export_path
        _STATE._arm_atexit()


def enabled() -> bool:
    return _STATE.enabled


def now() -> float:
    """The obs clock (monotonic seconds).  All repo timing flows through
    here -- the ``timing-outside-obs`` lint rule forbids raw
    ``time.perf_counter`` / ``time.time`` outside ``repro/obs/``."""
    return time.perf_counter()


def _annotation(name: str, args: Dict[str, Any]):
    """An entered ``jax.profiler.TraceAnnotation`` for one span."""
    cls = _STATE.annotation
    if cls is None:
        from jax.profiler import TraceAnnotation

        cls = _STATE.annotation = TraceAnnotation
    ann = cls(name, **args)
    ann.__enter__()
    return ann


def _append(event: Dict[str, Any]) -> None:
    with _STATE.lock:
        if len(_STATE.events) >= _MAX_EVENTS:
            _STATE.dropped += 1
            return
        _STATE.events.append(event)


class Span:
    """One traced region; use via ``with span("name", key=val): ...``."""

    __slots__ = ("name", "args", "_t0", "_ann")

    def __init__(self, name: str, args: Dict[str, Any]):
        self.name = name
        self.args = args
        self._t0 = 0
        self._ann = None

    def __enter__(self) -> "Span":
        self._ann = _annotation(self.name, self.args)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        _append({
            "ph": "X",
            "name": self.name,
            "ts": (self._t0 - _T0_NS) / 1e3,  # microseconds
            "dur": (t1 - self._t0) / 1e3,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": self.args,
        })
        return False


class _NullSpan:
    """The disabled path: a shared singleton whose enter/exit do nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


def span(name: str, **args: Any):
    """Nestable traced region.  Disabled -> returns a no-op singleton
    (no clock read, no allocation beyond the kwargs dict)."""
    if not _STATE.enabled:
        return _NULL_SPAN
    return Span(name, args)


class Timed:
    """Always-measuring timed region: ``.seconds`` is valid after exit.

    With ``metric=`` the duration is recorded into that metrics histogram
    (labels = the span args), so one ``with obs.timed(...)`` both feeds the
    trace (when enabled) and the always-on metrics registry.
    """

    __slots__ = ("name", "args", "metric", "seconds", "_t0", "_ann")

    def __init__(self, name: str, metric: Optional[str] = None,
                 **args: Any):
        self.name = name
        self.args = args
        self.metric = metric
        self.seconds = 0.0
        self._t0 = 0
        self._ann = None

    def __enter__(self) -> "Timed":
        if _STATE.enabled:
            self._ann = _annotation(self.name, self.args)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.seconds = (t1 - self._t0) / 1e9
        if self.metric is not None:
            from repro.obs.metrics import METRICS

            METRICS.histogram(self.metric, **self.args).record(self.seconds)
        if _STATE.enabled:
            _append({
                "ph": "X",
                "name": self.name,
                "ts": (self._t0 - _T0_NS) / 1e3,
                "dur": (t1 - self._t0) / 1e3,
                "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": self.args,
            })
        return False


def timed(name: str, metric: Optional[str] = None, **args: Any) -> Timed:
    return Timed(name, metric=metric, **args)


def event(name: str, **args: Any) -> None:
    """Instant event (``ph: "i"``) -- a point marker in the trace."""
    if not _STATE.enabled:
        return
    _append({
        "ph": "i",
        "s": "t",
        "name": name,
        "ts": (time.perf_counter_ns() - _T0_NS) / 1e3,
        "pid": os.getpid(),
        "tid": threading.get_ident(),
        "args": args,
    })


def trace_events() -> List[Dict[str, Any]]:
    """Snapshot of the buffered events (a shallow copy)."""
    with _STATE.lock:
        return list(_STATE.events)


def num_events() -> int:
    with _STATE.lock:
        return len(_STATE.events)


def dropped_events() -> int:
    """Events discarded past the buffer cap (surfaced by the ``[obs]`` exit
    summary so a truncated trace is never silent)."""
    with _STATE.lock:
        return _STATE.dropped


def reset() -> None:
    """Drop every buffered event (tests, repeated benchmark passes)."""
    with _STATE.lock:
        _STATE.events = []
        _STATE.dropped = 0


def export_trace(path: str) -> str:
    """Write the buffer as Chrome ``trace_event`` JSON; returns ``path``."""
    with _STATE.lock:
        events = list(_STATE.events)
        dropped = _STATE.dropped
    doc = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"producer": "repro.obs", "dropped_events": dropped},
    }
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path
