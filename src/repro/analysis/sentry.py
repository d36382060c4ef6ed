"""Recompile sentry: make "one compile per (kind, bucket)" assertable.

The PR 3 bug class: a weak-typed parameter (``jnp.full(shape, py_float)``
with no dtype) changes abstract value after the first EM update, so every
jitted training step silently retraces -- numerically invisible, 10-100x
slow.  Nothing in jax surfaces this; ``jax.monitoring`` compile events are
noisy (service-side lowerings fire too).  This sentry instead counts what
jit itself keys on -- the *abstract signature* of each call (shape, dtype,
weak_type per leaf) -- and cross-checks against the jitted object's own
cache size where jax exposes it (``pjit._cache_size``), plus the
``ProgramRegistry`` compile counter for the AOT/serve path.

Usage (also available as the ``compile_sentry`` pytest fixture)::

    with CompileSentry() as sentry:
        step = sentry.wrap(make_em_step(model), name="em_step")
        for _ in range(3):
            params, ll = step(params, x)
    sentry.assert_max_compiles(1, name="em_step")
    assert not sentry.findings   # no weak-type / promotion leaks

For serving, wrap nothing and use the registry delta::

    with CompileSentry(registry=engine.registry) as sentry:
        engine.submit(stream)
    assert sentry.registry_compiles() <= kinds * buckets
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro import obs

Signature = Tuple[Tuple[Any, ...], ...]


@dataclasses.dataclass(frozen=True)
class SentryFinding:
    """One detected compile-hygiene leak."""

    kind: str  # "weak-type-arg" | "weak-type-leak" | "dtype-promotion-leak"
    fn: str  # wrapped-function name
    message: str

    def __str__(self) -> str:
        return f"{self.kind} in {self.fn}: {self.message}"


def _leaf_aval(leaf: Any) -> Tuple[Any, ...]:
    """(shape, dtype, weak_type) of one argument leaf -- exactly the triple
    jit's dispatch cache keys on.  Non-array statics hash by repr."""
    import jax

    try:
        aval = jax.typeof(leaf)
    except TypeError:
        return ("static", repr(leaf), False)
    return (
        tuple(getattr(aval, "shape", ())),
        str(getattr(aval, "dtype", type(leaf).__name__)),
        bool(getattr(aval, "weak_type", False)),
    )


def _signature(args: tuple, kwargs: dict) -> Signature:
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs))
    return tuple(_leaf_aval(leaf) for leaf in leaves)


class CompileSentry:
    """Context manager counting compile-cache misses by abstract signature.

    ``wrap(fn, name)`` returns ``fn`` instrumented to record each call's
    abstract signature; the number of *distinct* signatures is the number
    of compiles jit must perform (its cache key), and pairs of signatures
    that differ only in ``weak_type`` or only in dtype are flagged as
    leaks -- the silent-retrace bug class.  When the wrapped object exposes
    ``_cache_size()`` (jitted functions do), the sentry cross-checks the
    observed cache growth against the signature count.
    """

    def __init__(self, registry: Optional[Any] = None):
        self.registry = registry
        self._reg_compiles0 = 0
        self._sigs: Dict[str, List[Signature]] = {}
        self._calls: Dict[str, int] = {}
        self._cache0: Dict[str, Optional[int]] = {}
        self._fns: Dict[str, Any] = {}
        self.findings: List[SentryFinding] = []
        # registry compile events observed while active, via the shared
        # obs hook (repro.compile emits; obs.events counts the metrics;
        # the sentry only *listens* -- nothing double counts)
        self.compile_events: List[Dict[str, Any]] = []
        self._listener: Optional[Callable] = None
        self.active = False

    # ------------------------------------------------------------- lifecycle
    def __enter__(self) -> "CompileSentry":
        self.active = True
        if self.registry is not None:
            self._reg_compiles0 = int(self.registry.stats["compiles"])
        self._listener = obs.on_compile(self.compile_events.append)
        return self

    def __exit__(self, *exc) -> None:
        self.active = False
        if self._listener is not None:
            obs.remove_compile_listener(self._listener)
            self._listener = None

    # --------------------------------------------------------------- wrapping
    def wrap(self, fn: Callable, name: Optional[str] = None) -> Callable:
        """Instrument ``fn``: every call records its abstract signature."""
        label = name or getattr(fn, "__name__", None) or repr(fn)
        self._sigs.setdefault(label, [])
        self._calls.setdefault(label, 0)
        self._fns[label] = fn
        if label not in self._cache0:
            size = getattr(fn, "_cache_size", None)
            self._cache0[label] = int(size()) if callable(size) else None

        def wrapped(*args, **kwargs):
            self._record(label, args, kwargs)
            return fn(*args, **kwargs)

        wrapped.__name__ = f"sentry[{label}]"
        return wrapped

    def _record(self, label: str, args: tuple, kwargs: dict) -> None:
        sig = _signature(args, kwargs)
        self._calls[label] += 1
        seen = self._sigs[label]
        if sig in seen:
            return
        for leaf in sig:
            shape, dtype, weak = leaf
            if weak and shape != () and shape != ("static",):
                self._report(SentryFinding(
                    "weak-type-arg", label,
                    f"weak-typed array argument {shape} {dtype}: its aval "
                    f"changes once an op touches it, forcing a retrace "
                    f"(give it an explicit dtype)"))
        for prev in seen:
            self._diff(label, prev, sig)
        seen.append(sig)

    def _diff(self, label: str, a: Signature, b: Signature) -> None:
        """Flag signature pairs that differ ONLY in weak_type / dtype --
        same shapes, so the caller almost certainly meant them to hit one
        compiled program."""
        if len(a) != len(b):
            return
        if any(la[0] != lb[0] for la, lb in zip(a, b)):
            return  # genuine shape polymorphism (bucketing) -- not a leak
        weak_only = all(la[:2] == lb[:2] for la, lb in zip(a, b))
        if weak_only:
            self._report(SentryFinding(
                "weak-type-leak", label,
                "two calls share every shape and dtype but differ in "
                "weak_type -- a weak-typed input is splitting the jit "
                "cache (the PR 3 class_prior bug class)"))
            return
        dtype_only = all(la[0] == lb[0] for la, lb in zip(a, b))
        if dtype_only:
            diffs = [
                f"{la[1]}->{lb[1]}"
                for la, lb in zip(a, b) if la[1] != lb[1]
            ]
            self._report(SentryFinding(
                "dtype-promotion-leak", label,
                f"two calls share every shape but differ in dtype "
                f"({', '.join(sorted(set(diffs))[:4])}) -- an implicit "
                f"promotion is splitting the jit cache"))

    def _report(self, finding: SentryFinding) -> None:
        if all(str(finding) != str(f) for f in self.findings):
            self.findings.append(finding)

    # ------------------------------------------------------------- accounting
    def signatures(self, name: str) -> Tuple[Signature, ...]:
        return tuple(self._sigs.get(name, ()))

    def compiles(self, name: Optional[str] = None) -> int:
        """Compiles attributable to the wrapped function(s): the jit cache
        growth when the object exposes it, else the distinct-signature
        count (identical by construction of jit's cache key)."""
        names = [name] if name is not None else list(self._sigs)
        total = 0
        for label in names:
            fn = self._fns.get(label)
            size = getattr(fn, "_cache_size", None)
            base = self._cache0.get(label)
            if callable(size) and base is not None:
                total += int(size()) - base
            else:
                total += len(self._sigs.get(label, ()))
        return total

    def registry_compiles(self) -> int:
        """ProgramRegistry compiles since ``__enter__`` (the AOT path)."""
        if self.registry is None:
            raise ValueError("CompileSentry was built without a registry")
        return int(self.registry.stats["compiles"]) - self._reg_compiles0

    # ------------------------------------------------------------- assertions
    def assert_max_compiles(self, limit: int, name: Optional[str] = None):
        got = self.compiles(name)
        if got > limit:
            raise AssertionError(
                f"recompile sentry: {got} compiles for "
                f"{name or 'all wrapped fns'} (limit {limit})\n"
                + self.report()
            )

    def assert_no_leaks(self) -> None:
        if self.findings:
            raise AssertionError(
                "recompile sentry found compile-hygiene leaks:\n"
                + "\n".join(f"  - {f}" for f in self.findings)
            )

    def report(self) -> str:
        lines = []
        for label, sigs in self._sigs.items():
            lines.append(
                f"  {label}: {self._calls[label]} call(s), "
                f"{len(sigs)} distinct signature(s), "
                f"{self.compiles(label)} compile(s)")
            for i, sig in enumerate(sigs):
                lines.append(f"    sig {i}: {sig}")
        for f in self.findings:
            lines.append(f"  finding: {f}")
        return "\n".join(lines) or "  (nothing wrapped)"
