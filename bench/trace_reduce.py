"""Reduction of a profiler trace to device busy time, kernel time and the
breakdown.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes.  Device operations
are the events of the ``XLA Ops`` line of each ``/device:<kind>:<n>`` plane;
the window is the host span the harness opens around the measured loop
(``WINDOW_SPAN``).  Busy time is the union of the device-op intervals that
fall inside the window, averaged over the chips that ran anything.  A
kernel's time is the summed device duration of the ops whose name, or any
of whose string stats, contains the kernel's name.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"

Interval = Tuple[int, int, str]      # (start_ns, end_ns, name)


def latest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _text(ev) -> str:
    parts = [ev.name]
    for _, v in getattr(ev, "stats", ()):
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def read(path: str):
    """(device ops per chip, host events) of one trace file, as plain
    intervals.  Device ops carry the text of their name and stats."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(int(e.start_ns), int(e.end_ns), _text(e))
                            for e in line.events]
            if ops:
                dev[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(int(e.start_ns), int(e.end_ns), e.name)
                         for e in line.events if e.duration_ns > 0]
    return dev, host


def window_of(host: Sequence[Interval]) -> Tuple[int, int]:
    spans = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return max(spans, key=lambda x: x[1] - x[0])


def _clip(iv: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    out = []
    for s, e, n in iv:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, n))
    return out


def merged(iv: Sequence[Interval]) -> List[Tuple[int, int]]:
    """Union of intervals, sorted and disjoint."""
    out: List[List[int]] = []
    for s, e, _ in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaves(ops: Sequence[Interval]) -> List[Interval]:
    """The ops that hold no other op: a loop or call op spans its body's
    ops on the same line, and would count their time twice."""
    srt = sorted(ops, key=lambda x: (x[0], -x[1]))
    parent = [False] * len(srt)
    stack: List[int] = []
    for i, (s, e, _) in enumerate(srt):
        while stack and srt[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= srt[stack[-1]][1]:
            parent[stack[-1]] = True
        stack.append(i)
    return [iv for iv, p in zip(srt, parent) if not p]


def _name(text: str) -> str:
    return text.split(" ", 1)[0]


def reduce(dev: Dict[str, List[Interval]], host: Sequence[Interval],
           kernels: Sequence[str], top: int = 10) -> dict:
    """busy_s, window_s, per-kernel seconds, and the breakdown."""
    lo, hi = window_of(host)
    window_s = (hi - lo) / 1e9
    busy, kernel_s = [], {k: 0.0 for k in kernels}
    op_time: Dict[str, float] = collections.Counter()
    gaps: List[Tuple[int, int]] = []
    for ops in dev.values():
        ops = _clip(ops, lo, hi)
        if not ops:
            continue
        union = merged(ops)
        busy.append(sum(e - s for s, e in union) / 1e9)
        for s, e, text in leaves(ops):
            op_time[_name(text)] += (e - s) / 1e9
        for s, e, text in ops:
            for k in kernels:
                if k in text:
                    kernel_s[k] += (e - s) / 1e9
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    if not busy:
        return {"busy_s": 0.0, "window_s": window_s, "kernel_s": kernel_s,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "kernel_s": kernel_s,
        "breakdown": {
            "device_ops": [[n, t] for n, t in op_time.most_common(top)],
            "idle_gaps": idle_causes(host, gaps, top),
        },
    }


def idle_causes(host: Sequence[Interval], gaps: Sequence[Tuple[int, int]],
                top: int, named: int = 2000) -> List[list]:
    """Idle seconds by what the host was doing.  Each of the ``named``
    longest gaps goes to the most specific host event that covers at least
    half of it (the latest-starting one); shorter gaps are summed apart."""
    inner = sorted(h for h in host if h[2] != WINDOW_SPAN)
    starts = [h[0] for h in inner]
    longest = max((e - s for s, e, _ in inner), default=0)
    by_cause: Dict[str, float] = collections.Counter()
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    for a, b in gaps[:named]:
        best, best_start = "host (no span)", -1
        for s, e, n in inner[bisect.bisect_left(starts, a - longest):
                             bisect.bisect_left(starts, b)]:
            if 2 * (min(e, b) - max(s, a)) >= b - a and s > best_start:
                best, best_start = n, s
        by_cause[best] += (b - a) / 1e9
    rest = gaps[named:]
    if rest:
        cap = (rest[0][1] - rest[0][0]) / 1e6
        by_cause[f"gaps under {cap:.3f} ms"] += sum(b - a for a, b in rest) / 1e9
    return [[n, t] for n, t in by_cause.most_common(top)]
