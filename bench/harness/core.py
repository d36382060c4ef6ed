"""What every cell shares: the benchmark's files found by name, the chip
check, the compile counter, host spans, the comparisons and the last line.

Files (all under ``bench/``):

* ``configs/<config>.json``: a configuration's sizes; its ``reference``
  names the plain reference module beside it;
* ``traffic/<mix>.json``: a traffic mix's parameters; ``driver`` names the
  general driver that reads them, ``harness/<driver>.py`` (the contract a
  driver keeps is in :mod:`harness.main`);
* ``limits/<cell>.json``: the limit of each number ``correct`` compares;
* ``metrics/<metric>.py``: one per-layer metric's reader, ``read(run)``;
* ``peaks.json``: per ``device_kind`` peak rates, with their source.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    pass


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with its files."""

    def __init__(self, name: str, spec: Optional[dict] = None, bench: pathlib.Path = BENCH):
        self.spec = spec if spec is not None else load_json(bench.parent / "BENCHMARK.json")
        found = [w for w in self.spec["workloads"] if w["name"] == name]
        if not found:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.workload = found[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        self.config_entry = [c for c in self.spec["configs"]
                             if c["name"] == self.workload["config"]][0]
        self.config = load_json(bench.parent / self.config_entry["file"])
        self.traffic = load_json(bench / "traffic" / f"{self.workload['traffic']}.json")
        limits = bench / "limits" / f"{name}.json"
        self.limits = load_json(limits) if limits.exists() else {}
        self.bench = bench

    def reference(self):
        return load_module(self.bench / "configs" / f"{self.config['reference']}.py")

    def driver(self):
        """The module ``harness/<driver>.py`` that the traffic mix names."""
        name = self.traffic["driver"]
        path = self.bench / "harness" / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"traffic {self.workload['traffic']!r} names driver "
                                    f"{name!r}, and there is no {path}")
        return load_module(path)

    def metrics(self, section: str) -> List[dict]:
        """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
        return [m for m in self.spec[section]
                if "workloads" not in m or self.name in m["workloads"]]


def load_module(path: pathlib.Path):
    """The module in the file ``path``, loaded once per file."""
    name = f"bench_{path.parent.name}_{path.stem}".replace("-", "_").replace(".", "_")
    mod = sys.modules.get(name)
    if mod is not None and pathlib.Path(mod.__file__) == pathlib.Path(path):
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def program_config(config: dict):
    """The program's config object for a benchmark configuration file."""
    from repro.configs import EinetConfig

    fields = {k: config[k] for k in EinetConfig.__dataclass_fields__ if k in config}
    if "pd_axes" in fields:
        fields["pd_axes"] = tuple(fields["pd_axes"])
    return EinetConfig(**fields)


def peaks(kind: str, path: pathlib.Path = BENCH / "peaks.json") -> dict:
    table = load_json(path)["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in the peak table")
    return table[kind]


def devices(chips: int, require_tpu: bool = True):
    """The first ``chips`` devices; a run without that many TPUs stops."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``$JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), keeping every program, however quick."""
    import jax

    from repro.launch import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX traces and backend compiles (cache loads included) while
    ``active``; the window should see none."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        self.names: List[str] = []
        self.cache = {"cache_hits": 0, "cache_misses": 0}

        def listener(event, duration, **kw):
            if self.active and event in self.EVENTS:
                self.count += 1
                self.names.append(f"{event.rsplit('/', 1)[-1]}:{kw.get('fun_name', '?')}")

        def cache_listener(event, **kw):
            name = event.rsplit("/", 1)[-1]
            if name in self.cache:
                self.cache[name] += 1

        jax.monitoring.register_event_duration_secs_listener(listener)
        jax.monitoring.register_event_listener(cache_listener)


class Annotation:
    """A host span in the profiler trace, opened and closed by hand (it may
    cover a window that a loop or a generator walks through)."""

    def __init__(self, name: str):
        import jax

        self._ann = jax.profiler.TraceAnnotation(name)

    def start(self):
        self._ann.__enter__()

    def stop(self):
        self._ann.__exit__(None, None, None)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Profiler over a measured window (when ``on``).  Its ``bench.window``
    span covers the first ``seconds``; the profiler itself starts before the
    window and is stopped only after it, so its start and its teardown fall
    outside what the window times.

    While the profiler runs, ``repro.obs`` tracing is on too (unless
    ``obs`` is False), so the program's spans land in the trace.  A driver
    hands the tracer each compiled program that the window runs
    (:meth:`add_program`), so that every device op is charged to its named
    scope."""

    def __init__(self, on: bool, seconds: float, obs: bool = True):
        self.on = on
        self.seconds = seconds
        self.obs = obs
        self.op_scopes: Dict[str, str] = {}
        self.dir: Optional[str] = None
        self.running = False
        self.open = False
        self._window = None

    def add_program(self, hlo_text: str) -> None:
        """Look up each op's scope in a compiled program's HLO text
        (``jax.stages.Compiled.as_text()``); called in set-up, only when
        ``on``."""
        from harness import scopes

        self.op_scopes.update(scopes.hlo_op_scopes(hlo_text))

    def start(self):
        """Start the profiler, then ``repro.obs`` tracing (before the window
        opens)."""
        if not self.on:
            return
        import tempfile

        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)
        self.running = True
        if self.obs:
            from repro import obs

            obs.configure(trace=True)

    def open_window(self):
        """Open the ``bench.window`` span: the first thing the window does."""
        if self.running:
            self._window = Annotation("bench.window")
            self._window.start()
            self.t0 = time.perf_counter()
            self.open = True

    def tick(self):
        """Called once per step of the window; closes the span after
        ``seconds``."""
        if self.open and time.perf_counter() - self.t0 >= self.seconds:
            self._window.stop()
            self.open = False

    def stop(self):
        """Close the span if still open, turn ``repro.obs`` tracing off and
        stop the profiler (after the window has closed)."""
        if self.open:
            self._window.stop()
            self.open = False
        if self.running:
            import jax

            if self.obs:
                from repro import obs

                obs.configure(trace=False)
            jax.profiler.stop_trace()
            self.running = False

    def events(self) -> List[tuple]:
        """Stop, flatten the trace with the program's spans and each device
        op's scope (:func:`harness.scopes.events_from_xplane`), and remove
        it."""
        import shutil

        from harness import scopes, trace

        self.stop()
        try:
            return scopes.events_from_xplane(trace.find_xplane(self.dir), self.op_scopes)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None

    def reduce(self) -> Optional[dict]:
        """:func:`reduce_window` of the trace; None where nothing was traced."""
        if self.dir is None:
            return None
        return reduce_window(self.events())


def reduce_window(events: List[tuple]) -> dict:
    """:func:`harness.scopes.reduce` over the ``bench.window`` span."""
    from harness import scopes, trace

    window = trace.spans(events, "bench.window")
    if not window:
        raise RuntimeError("the trace holds no bench.window span")
    return scopes.reduce(events, window[0])


def make_weights(ref, layout, seed_word: int):
    """The benchmark's weights from the seed, in one jitted call on the
    device: the reference's parameters (returned to the host) and the same
    values placed in the program's pytree (left on the device)."""
    import jax

    def both(key):
        r = ref.init(key)
        return r, layout.to_program(r)

    r, params = jax.jit(both)(jax.random.PRNGKey(seed_word))
    return jax.device_get(r), params


# ------------------------------------------------------------- comparisons
def leaf_norms(tree) -> List[float]:
    import jax
    import numpy as np

    return [float(np.linalg.norm(np.asarray(a, np.float64).ravel()))
            for a in jax.tree_util.tree_leaves(tree)]


def norm_gaps(prog, ref, keep) -> List[float]:
    """Per leaf, |‖prog‖ - ‖ref‖| over the larger of ‖ref‖ and the median
    kept leaf's ‖ref‖; only leaves where ``keep`` is True."""
    import numpy as np

    p, r = leaf_norms(prog), leaf_norms(ref)
    kept = [x for x, k in zip(r, keep) if k]
    med = float(np.median(kept)) if kept else 0.0
    return [abs(a - b) / max(b, med, 1e-30) for a, b, k in zip(p, r, keep) if k]


class Checks:
    """The numbers ``correct`` compares, each with its limit (pass: value
    <= limit)."""

    def __init__(self, limits: Dict[str, float]):
        self.limits = limits
        self.values: Dict[str, float] = {}

    def add(self, name: str, value: float) -> None:
        if name not in self.limits:
            raise KeyError(f"no limit for {name!r}")
        self.values[name] = float(value)

    def ok(self) -> bool:
        import math

        return bool(self.values) and all(
            math.isfinite(v) and v <= self.limits[k] for k, v in self.values.items())

    def table(self) -> Dict[str, Dict[str, float]]:
        return {k: {"value": v, "limit": self.limits[k]} for k, v in self.values.items()}


def log(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


def memory_peak(devs) -> Optional[int]:
    peaks_ = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


@contextlib.contextmanager
def no_op():
    yield
