"""The program's own spans in a traced run, for the per-layer metrics that
read host time by layer.

The runtime opens ``jax.profiler.TraceAnnotation``s under bare names
(``rt.retrieval``, ``rt.commit``, ...; ids are stats, not part of the
name).  This module reads them from the trace file the harness wrote
(``run.TRACE_DIR``) with ``trace_reduce.read``, and sums each name's
seconds inside the harness's window span.  A program that opens no such
span gives no reading, and its metric is left out of the result line.
"""
from __future__ import annotations

import collections
import os
from typing import Dict, List, Sequence

import trace_reduce

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         ".bench_trace")

_cache: Dict[tuple, tuple] = {}


def span_seconds(host: Sequence[trace_reduce.Interval], lo: int, hi: int) -> Dict[str, float]:
    """Seconds of each host event name inside [lo, hi].  Events of one name
    that overlap count once; an event holding events of other names counts
    their time as its own."""
    by_name: Dict[str, List[trace_reduce.Interval]] = collections.defaultdict(list)
    for iv in trace_reduce._clip(host, lo, hi):
        by_name[iv[2]].append(iv)
    return {n: sum(e - s for s, e in trace_reduce.merged(iv)) / 1e9
            for n, iv in by_name.items()}


def traced(trace_dir: str = TRACE_DIR):
    """(window seconds, span seconds by name) of the newest trace under
    ``trace_dir``, read once per file."""
    path = trace_reduce.latest_xplane(trace_dir)
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _, host = trace_reduce.read(path)
        lo, hi = trace_reduce.window_of(host)
        _cache.clear()
        _cache[key] = ((hi - lo) / 1e9, span_seconds(host, lo, hi))
    return _cache[key]


def span_ms(ctx, *names, trace_dir: str = TRACE_DIR):
    """Wall milliseconds a completed request spent in the program's spans
    of these names, inside the traced window: the spans' seconds summed (a
    span includes the spans inside it) over the requests completed in the
    window.  None when the run was not traced, when the trace file is not
    this run's, or when it holds none of the spans."""
    if ctx.trace is None or not ctx.served:
        return None
    try:
        window_s, spans = traced(trace_dir)
    except (FileNotFoundError, ValueError):
        return None
    if abs(window_s - ctx.trace["window_s"]) > 1e-6 * max(1.0, window_s):
        return None
    if not any(n in spans for n in names):
        return None
    return 1e3 * sum(spans.get(n, 0.0) for n in names) / len(ctx.served)
