"""The flattening of a ``jax.profiler`` trace, and its reduction with the
program's own spans and named scopes: idle gaps split by the innermost host
span, device time per named scope, and the per-step layer times read from
them, beside :func:`harness.trace.reduce_trace`'s busy time and top ops.

The program writes two things into the trace besides its device ops:

* ``repro.obs`` spans on the host plane (``train.copy``,
  ``train.dispatch``, ``train.sync``, ``train.record``; ``serve.*``), when
  ``repro.obs`` tracing is on while the profiler runs;
* the HLO ``op_name`` of every device op, the path of ``jax.named_scope``
  names it was traced under (``jit(step)/em.estep/jvp(plan.gather)/...``).
  ``jax.profiler.ProfileData`` gives an ``XLA Ops`` event only its name and
  timing stats (on a v5e: ``device_offset_ps``, ``device_duration_ps``,
  ``Time Scale Multiplier``), so the ``op_name`` is read from the compiled
  program's HLO text by op name (:func:`hlo_op_scopes`); op names are unique
  within a program.  A fusion carries the ``op_name`` of its root op, so a
  fusion is charged to the scope of its root.

Events are ``(plane, line, name, start_ns, duration_ns, scope)``: the op's
innermost program scope last (``""`` where it has none).
:func:`harness.trace.reduce_trace` reads the first five fields alone, and
:func:`reduce` reads 5-tuples too, as ops under no scope.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace

PROGRAM_SPAN_PREFIXES = ("train.", "serve.")
# the program's scope names: plan.<kind>, einet.leaf, em.estep, em.mstep, ...
SCOPE_RE = re.compile(r"(?<![\w.])(?:plan|einet|em)\.[a-z_]+")
# one instruction of HLO text with its metadata: name, op_name
HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"', re.M)

# layer -> the innermost scopes charged to it
LAYERS = {
    "leaf": ("einet.leaf", "em.leaf_stats"),
    "einsum": ("plan.fused", "plan.gather", "plan.layer"),
    "mstep": ("em.mstep",),
}


def scope_of(op_name: str) -> str:
    """The innermost program scope in an HLO ``op_name`` path; where XLA
    merged several names (``a;b``), the first."""
    found = SCOPE_RE.findall(op_name.split(";", 1)[0])
    return found[-1] if found else ""


def hlo_op_scopes(hlo_text: str) -> Dict[str, str]:
    """Op name -> innermost program scope, from a compiled program's HLO
    text (``jax.stages.Compiled.as_text()``)."""
    return {name: scope_of(op) for name, op in HLO_OP.findall(hlo_text)}


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_SPAN_PREFIXES)


def events_from_xplane(path: str, op_scopes: Dict[str, str]) -> List[tuple]:
    """Flatten the device and host planes of one ``.xplane.pb`` file: each
    device op and program run, the host's program launches, the benchmark's
    spans and the program's, each device op's scope (looked up by op name
    in ``op_scopes``) as a sixth field."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: List[tuple] = []
    for plane in data.planes:
        device = plane.name.startswith(trace.DEVICE_PREFIX)
        if not (device or plane.name == trace.HOST_PLANE):
            continue
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for e in line.events:
                scope = ""
                if device and line.name == "XLA Ops":
                    scope = op_scopes.get(trace.op_name(e.name), "")
                elif not device and not (e.name.startswith(trace.SPAN_PREFIX)
                                         or is_program_span(e.name)
                                         or e.name == trace.LAUNCH):
                    continue
                out.append((plane.name, line.name, e.name, float(e.start_ns),
                            float(e.duration_ns), scope))
    return out


def host_spans(events: Sequence[tuple]) -> List[Tuple[float, float, str]]:
    """The benchmark's spans (but ``bench.window``) and the program's."""
    return [(e[3], e[3] + e[4], e[2]) for e in events
            if e[0] == trace.HOST_PLANE and e[2] != "bench.window"
            and (e[2].startswith(trace.SPAN_PREFIX) or is_program_span(e[2]))]


def split_gap(spans: Sequence[Tuple[float, float, str]], a: float,
              b: float) -> Dict[str, float]:
    """Charge each piece of the idle gap [a, b) to the innermost (shortest)
    span that covers it, ``host`` where none does; the pieces lie between
    the span edges inside the gap."""
    inside = [s for s in spans if s[1] > a and s[0] < b]
    cuts = sorted({a, b} | {x for s, e, _ in inside for x in (s, e) if a < x < b})
    out: Dict[str, float] = collections.Counter()
    for lo, hi in zip(cuts, cuts[1:]):
        cover = [(e - s, name) for s, e, name in inside if s <= lo and e >= hi]
        out[min(cover)[1] if cover else "host"] += hi - lo
    return out


def holders(ops: Sequence[tuple]) -> set:
    """Indices of the ops (start, end, scope, ...) of one chip whose interval
    holds a shorter op of the same scope: a loop op (``while``) and the ops
    of its body are all reported, and the body's ops alone are the work."""
    out = set()
    by_scope: Dict[str, List[int]] = collections.defaultdict(list)
    for i, op in enumerate(ops):
        by_scope[op[2]].append(i)
    for idx in by_scope.values():
        idx.sort(key=lambda i: (ops[i][0], -ops[i][1]))
        for k, i in enumerate(idx):
            a, b = ops[i][0], ops[i][1]
            m = k + 1
            while m < len(idx) and ops[idx[m]][0] < b:
                j = idx[m]
                if ops[j][1] <= b and ops[j][1] - ops[j][0] < b - a:
                    out.add(i)
                    break
                m += 1
    return out


def reduce(events: Sequence[tuple], window: Tuple[float, float]) -> Dict:
    """:func:`harness.trace.reduce_trace` of the window, and beside it:

    * ``idle_split``: chip 0's idle gaps split by the innermost span, from
      the benchmark or the program (:func:`split_gap`), in s;
    * ``scope_seconds``: device time per innermost program scope, mean over
      chips, clipped to the window (``""``: ops under no program scope); an
      op that holds others of its scope (:func:`holders`) is left out;
    * ``op_scopes``: each op name's scope;
    * ``span_seconds``: host time per program span name, clipped to the
      window;
    * ``steps``: ``bench.step`` spans wholly inside the window.
    """
    lo, hi = window
    base = trace.reduce_trace(events, window)
    spans = host_spans(events)
    names = trace.chips(events)
    scope_ns: Dict[str, float] = collections.Counter()
    op_scopes: Dict[str, str] = {}
    idle: Dict[str, float] = collections.Counter()
    for i, chip in enumerate(names):
        off = trace.host_offset_ns(events, chip)
        ops = [(e[3] + off, e[3] + off + e[4], e[5] if len(e) > 5 else "",
                trace.op_name(e[2]))
               for e in events if e[0] == chip and e[1] == "XLA Ops"]
        held = holders(ops)
        for k, (a, b, scope, op) in enumerate(ops):
            op_scopes[op] = scope
            a2, b2 = max(a, lo), min(b, hi)
            if b2 > a2 and k not in held:
                scope_ns[scope] += (b2 - a2) / len(names)
        if i == 0:
            busy = trace.union(trace.clip([(a, b) for a, b, _, _ in ops], lo, hi))
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    for name, ns in split_gap(spans, a, b).items():
                        idle[name] += ns
    span_ns: Dict[str, float] = collections.Counter()
    for s, e, name in spans:
        if is_program_span(name) and min(e, hi) > max(s, lo):
            span_ns[name] += min(e, hi) - max(s, lo)
    base.update({
        "idle_split": [[k, v * 1e-9] for k, v in
                       sorted(idle.items(), key=lambda kv: -kv[1])],
        "scope_seconds": {k: v * 1e-9 for k, v in scope_ns.items()},
        "op_scopes": op_scopes,
        "span_seconds": {k: v * 1e-9 for k, v in span_ns.items()},
        "steps": base["spans_inside"].get("bench.step", 0),
    })
    return base


def layer_ms(reduced: Optional[Dict]) -> Dict[str, float]:
    """The per-step layer times of a :func:`reduce` result, in ms: host time
    in ``train.copy``, ``train.dispatch`` and ``train.sync``, and device time
    under the leaf, einsum and M-step scopes (:data:`LAYERS`), each over the
    ``bench.step`` spans wholly inside the window.  A time the trace has
    nothing for is left out."""
    if not reduced or not reduced.get("steps"):
        return {}
    per_step = 1e3 / reduced["steps"]
    out = {}
    for span in ("copy", "dispatch", "sync"):
        if f"train.{span}" in reduced["span_seconds"]:
            out[f"train_{span}_ms"] = reduced["span_seconds"][f"train.{span}"] * per_step
    for layer, scopes in LAYERS.items():
        found = [reduced["scope_seconds"][s] for s in scopes
                 if s in reduced["scope_seconds"]]
        if found:
            out[f"train_{layer}_ms"] = sum(found) * per_step
    return out
