"""The readers of the program's spans: span seconds inside the window, the
per-request metrics, and a traced tiny cell that reports them.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH,
                os.path.join(BENCH, "traffic"), HERE]

import jax  # noqa: E402

import run  # noqa: E402
import spans  # noqa: E402
import tiny  # noqa: E402
import trace_reduce as trace  # noqa: E402

SEED = 2**35 + 91
SPAN_METRICS = ("retrieval.search_ms.single", "scheduler.host_ms.single",
                "tree.commit_ms.single")


def test_span_seconds_by_hand():
    ms = 1_000_000
    host = [(0, 100 * ms, trace.WINDOW_SPAN),
            (-10 * ms, 10 * ms, "rt.retrieval"),          # half inside
            (20 * ms, 50 * ms, "rt.commit"),
            (25 * ms, 40 * ms, "rt.tree.demote"),         # inside the commit
            (30 * ms, 35 * ms, "rt.tree.demote"),         # overlaps the first: once
            (90 * ms, 120 * ms, "rt.commit")]             # clipped at the window
    s = spans.span_seconds(host, 0, 100 * ms)
    assert s["rt.retrieval"] == pytest.approx(0.010)
    assert s["rt.commit"] == pytest.approx(0.040)
    assert s["rt.tree.demote"] == pytest.approx(0.015)


def record(trace_dir, names):
    """A trace with a window span holding three of each span, each with a
    keyword stat."""
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for i in range(3):
            for n in names:
                with jax.profiler.TraceAnnotation(n, req_id=i):
                    sum(range(20000))
    jax.profiler.stop_trace()


def test_span_seconds_of_a_recorded_trace_with_stats(tmp_path):
    """A span recorded with a keyword keeps its bare name: the keyword is a
    stat, so spans group by name."""
    record(tmp_path, ["rt.commit"])
    dev, host = trace.read(trace.latest_xplane(str(tmp_path)))
    lo, hi = trace.window_of(host)
    s = spans.span_seconds(host, lo, hi)
    assert [n for _, _, n in host].count("rt.commit") == 3
    assert 0 < s["rt.commit"] <= (hi - lo) / 1e9


def test_span_ms_by_hand(tmp_path):
    record(tmp_path, ["rt.commit", "rt.schedule"])
    window_s, s = spans.traced(str(tmp_path))
    ctx = run.Context(setup_s=1.0, window_s=2.0, latencies=[0.5], served=[object()] * 4,
                      trace={"window_s": window_s}, work=None, peak={})
    assert spans.span_ms(ctx, "rt.commit", trace_dir=str(tmp_path)) == \
        pytest.approx(1e3 * s["rt.commit"] / 4)
    assert spans.span_ms(ctx, "rt.commit", "rt.schedule", trace_dir=str(tmp_path)) == \
        pytest.approx(1e3 * (s["rt.commit"] + s["rt.schedule"]) / 4)
    # a program that opens no such span, an untraced run, another run's trace
    assert spans.span_ms(ctx, "rt.retrieval", trace_dir=str(tmp_path)) is None
    untraced = run.Context(1.0, 2.0, [0.5], [object()], None, None, {})
    assert spans.span_ms(untraced, "rt.commit", trace_dir=str(tmp_path)) is None
    other = run.Context(1.0, 2.0, [0.5], [object()], {"window_s": window_s + 1.0}, None, {})
    assert spans.span_ms(other, "rt.commit", trace_dir=str(tmp_path)) is None
    assert spans.span_ms(ctx, "rt.commit", trace_dir=str(tmp_path / "none")) is None


def test_traced_cell_reports_the_span_metrics(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    mix = dict(tiny.TINY_MIX, loop="single", max_new_tokens=1, check_requests=6,
               targets="unique", passages=400)
    root = tiny.make_root(str(tmp_path / "co"), {"unique.single": mix})
    res = run.execute(root, "tiny.unique.single", SEED, 1.0, True,
                      require_tpu=False, peaks=tiny.PEAKS)
    assert res["correct"]
    m = res["metrics"]
    assert {"mfu.single", "device.idle_share.single"} <= set(m)
    for k in SPAN_METRICS:
        assert m[k]["unit"] == "ms" and m[k]["value"] > 0, k
