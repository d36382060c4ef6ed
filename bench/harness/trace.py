"""Reduction of a ``jax.profiler`` trace to device busy time, the top
device operations and the benchmark's spans inside the window.

The trace is first flattened into event tuples
``(plane, line, name, start_ns, duration_ns[, scope])`` by
:func:`harness.scopes.events_from_xplane`; everything else works on that
list, so the self-checks run it on a small recorded trace without a chip.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO operation and ``XLA Modules`` one per program run.
The device clock is offset from the host clock.  :func:`host_offset_ns`
estimates that offset per chip from the host's program launches
(``PJRT_LoadedExecutable_Execute``), paired in order with the chip's program
runs: a run starts no earlier than its launch, so the largest (launch - run
start) is the least offset consistent with every pair.
"""

from __future__ import annotations

import collections
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, str, str, float, float]  # plane, line, name, start_ns, dur_ns

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
LAUNCH = "PJRT_LoadedExecutable_Execute"
SPAN_PREFIX = "bench."


def find_xplane(directory: str) -> str:
    for root, _, files in os.walk(directory):
        for name in files:
            if name.endswith(".xplane.pb"):
                return os.path.join(root, name)
    raise FileNotFoundError(f"no .xplane.pb under {directory}")


def op_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def chips(events: Sequence[Event]) -> List[str]:
    return sorted({e[0] for e in events if e[0].startswith(DEVICE_PREFIX)},
                  key=lambda p: int(p[len(DEVICE_PREFIX):]))


def host_offset_ns(events: Sequence[Event], chip: str) -> float:
    launches = sorted(e[3] for e in events if e[0] == HOST_PLANE and e[2] == LAUNCH)
    runs = sorted(e[3] for e in events if e[0] == chip and e[1] == "XLA Modules")
    n = min(len(launches), len(runs))
    if n == 0:
        return 0.0
    return max(launches[i] - runs[i] for i in range(n))


def spans(events: Sequence[Event], name: str) -> List[Tuple[float, float]]:
    return sorted((e[3], e[3] + e[4]) for e in events
                  if e[0] == HOST_PLANE and e[2] == name)


def reduce_trace(events: Sequence[Event], window: Tuple[float, float]) -> Dict:
    """Busy and idle time of every chip inside ``window`` (host-clock ns),
    the top device operations, and how many of each benchmark span lie
    wholly inside the window."""
    lo, hi = window
    per_chip = []
    op_time: Dict[str, float] = collections.Counter()
    host_spans = [(e[3], e[3] + e[4], e[2]) for e in events
                  if e[0] == HOST_PLANE and e[2].startswith(SPAN_PREFIX) and e[2] != "bench.window"]
    names = chips(events)
    for chip in names:
        off = host_offset_ns(events, chip)
        ops = [(e[3] + off, e[3] + off + e[4], e[2]) for e in events
               if e[0] == chip and e[1] == "XLA Ops"]
        busy = union(clip([(a, b) for a, b, _ in ops], lo, hi))
        busy_ns = sum(b - a for a, b in busy)
        per_chip.append({"chip": chip, "busy_ns": busy_ns, "offset_ns": off})
        for a, b, name in ops:
            a2, b2 = max(a, lo), min(b, hi)
            if b2 > a2:
                op_time[op_name(name)] += (b2 - a2) / len(names)
    window_ns = hi - lo
    inside = collections.Counter(name for s, e, name in host_spans if s >= lo and e <= hi)
    busy_mean = sum(c["busy_ns"] for c in per_chip) / max(len(per_chip), 1)
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_mean * 1e-9,
        "idle_share": 1.0 - busy_mean / window_ns if window_ns > 0 else None,
        "chips": per_chip,
        "spans_inside": dict(inside),
        "op_seconds": {k: v * 1e-9 for k, v in op_time.items()},
        "device_ops": [[k, v * 1e-9] for k, v in op_time.most_common(10)],
    }

