"""Serving metrics: timeline arithmetic, percentile aggregation, overlap
accounting."""
import pytest

from repro.serving.metrics import (RequestTimeline, ServingMetrics,
                                   percentiles)


def test_percentiles_known_values():
    p = percentiles([float(i) for i in range(1, 101)])
    assert p["mean"] == pytest.approx(50.5)
    assert p["p50"] == pytest.approx(50.5)
    assert p["p90"] == pytest.approx(90.1)
    assert p["p99"] == pytest.approx(99.01)
    assert p["max"] == 100.0


def test_percentiles_empty():
    p = percentiles([])
    assert p == {"mean": 0.0, "p50": 0.0, "p90": 0.0, "p99": 0.0, "max": 0.0}


def _timeline(**kw):
    tl = RequestTimeline(req_id=0, arrival=10.0)
    for k, v in kw.items():
        setattr(tl, k, v)
    return tl


def test_ttft_tpot_queueing():
    tl = _timeline(search_start=10.0, search_end=10.5, queue_enter=10.5,
                   final_prefill_start=10.6, first_token=11.0,
                   token_times=[11.2, 11.4, 11.6])
    assert tl.ttft == pytest.approx(1.0)
    assert tl.tpot == pytest.approx(0.2)
    assert tl.queueing == pytest.approx(0.1)


def test_overlap_accounting():
    # speculative prefill started mid-search: only the pre-launch part of
    # the search is on the critical path
    tl = _timeline(search_start=0.0, search_end=1.0, final_prefill_start=0.3,
                   first_token=1.5)
    assert tl.search_time == pytest.approx(1.0)
    assert tl.non_overlapped_search == pytest.approx(0.3)
    # no prefill overlap (sequential behaviour): full search is serial
    tl2 = _timeline(search_start=0.0, search_end=1.0, first_token=2.0)
    assert tl2.non_overlapped_search == pytest.approx(1.0)
    # prefill started after search finished: zero overlap
    tl3 = _timeline(search_start=0.0, search_end=1.0,
                    final_prefill_start=2.0, first_token=3.0)
    assert tl3.non_overlapped_search == pytest.approx(1.0)


def test_summary_aggregates():
    m = ServingMetrics()
    for i, (ft, spec) in enumerate([(1.0, True), (2.0, False)]):
        tl = m.timeline(i, 0.0)
        tl.first_token = ft
        tl.speculative_hit = spec
        tl.hit_docs, tl.n_docs = 1, 2
        tl.token_times = [ft + 0.1]
    unserved = m.timeline(99, 0.0)        # never completed: excluded
    assert unserved.first_token < 0
    m.record_iteration("prefill", 1)
    m.record_iteration("decode", 2)
    m.record_iteration("decode", 4)
    s = m.summary()
    assert s["completed"] == 2
    assert s["ttft"]["mean"] == pytest.approx(1.5)
    assert s["mean_decode_batch"] == pytest.approx(3.0)
    assert s["max_decode_batch"] == 4
    assert s["prefill_iterations"] == 1
    assert s["speculative_hits"] == 1
    assert s["doc_hit_rate"] == pytest.approx(0.5)
    assert "TTFT" in m.format_report()


def test_span_adds_wall_seconds_inclusive_of_inner_spans(monkeypatch):
    from repro.serving import metrics as metrics_mod
    ticks = iter([1.0, 1.5, 2.25, 4.0, 10.0, 10.5])
    monkeypatch.setattr(metrics_mod.time, "perf_counter", lambda: next(ticks))
    m = ServingMetrics()
    with m.span("rt.commit", req_id=7):          # 1.0 .. 4.0
        with m.span("rt.tree.demote"):           # 1.5 .. 2.25
            pass
    with m.span("rt.commit", req_id=8) as sp:    # 10.0 .. 10.5
        pass
    assert sp.seconds == pytest.approx(0.5)
    assert m.host_seconds["rt.commit"] == pytest.approx(3.5)
    assert m.host_seconds["rt.tree.demote"] == pytest.approx(0.75)
    assert m.summary()["host_seconds"] == {"rt.commit": pytest.approx(3.5),
                                           "rt.tree.demote": pytest.approx(0.75)}


def test_span_records_even_when_the_block_raises():
    m = ServingMetrics()
    with pytest.raises(KeyError):
        with m.span("rt.schedule"):
            raise KeyError("x")
    assert m.host_seconds["rt.schedule"] >= 0.0 and "rt.schedule" in m.host_seconds


def test_report_labels_runtime_clock_and_lists_host_spans():
    m = ServingMetrics()
    tl = m.timeline(0, 0.0)
    tl.first_token = 0.25
    rep = m.format_report()
    head, _, rest = rep.partition("runtime clock")
    assert "not wall time" in rest.splitlines()[0]
    for label in ("TTFT (ms)", "TPOT (ms)", "queueing (ms)", "search (ms)",
                  "non-overlapped search"):
        assert label not in head and label in rest
    assert "host seconds by span" not in rep     # nothing timed yet
    m.host_seconds["rt.retrieval"] += 0.002
    m.host_seconds["rt.commit"] += 0.031
    rep = m.format_report()
    block = rep.split("host seconds by span", 1)[1].splitlines()[1:]
    assert [ln.split(":")[0].strip() for ln in block] == ["rt.commit",
                                                          "rt.retrieval"]
    assert "0.031000" in block[0] and "0.002000" in block[1]


# ---------------------------------------------------------------------------
# degenerate inputs: zero completed requests, all-idle replicas (PR 6)
# ---------------------------------------------------------------------------

def test_zero_completed_requests_report():
    """A run where nothing completed (all shed, or an empty trace) must
    still summarize and render — the front-door driver prints a FleetMetrics
    report even when the cache absorbed every request."""
    m = ServingMetrics()
    s = m.summary()
    assert s["completed"] == 0
    assert all(s["ttft"][k] == 0.0 for k in ("mean", "p50", "p90", "p99"))
    assert s["doc_hit_rate"] == 0.0
    rep = m.format_report()
    assert "TTFT" in rep and "nan" not in rep
    # an opened-but-never-finished timeline stays excluded, not crashing
    m.timeline(0, 0.0)
    assert m.summary()["completed"] == 0
    assert "nan" not in m.format_report()


def test_fleet_metrics_all_idle_replica():
    from repro.serving.metrics import FleetMetrics
    fleet = FleetMetrics(router_stats={"policy": "affinity"})
    fleet.add_replica("replica0", ServingMetrics())   # never served anything
    busy = ServingMetrics()
    tl = busy.timeline(1, 0.0)
    tl.first_token = 0.5
    fleet.add_replica("replica1", busy)
    s = fleet.summary()
    assert s["replicas"] == 2 and s["completed"] == 1
    assert s["ttft"]["mean"] == pytest.approx(0.5)
    rep = fleet.format_report()
    assert "replica0" in rep and "replica1" in rep and "nan" not in rep
    # no front-door stats attached: no front-door block in the report
    assert "front door" not in rep


def test_fleet_metrics_renders_frontdoor_block():
    from repro.serving.frontdoor import TenantSLO, make_frontdoor
    from repro.serving.metrics import FleetMetrics
    import numpy as np
    from repro.retrieval.corpus import Request

    fd = make_frontdoor(capacity=8, ttl=1e9, sim_threshold=1.0,
                        slos={"acme": TenantSLO(ttft_target=0.5)},
                        init_service=1e-6, min_replicas=1, max_replicas=2,
                        autoscale=True, cooldown=0.0, scale_up_backlog=0.5,
                        scale_down_backlog=0.1)
    r = Request(req_id=0, arrival=0.0,
                query_vec=np.ones(4, np.float32),
                question_tokens=np.arange(4, dtype=np.int32),
                target_doc=0, output_len=1, tenant="acme")
    assert fd.handle(r, 0.0).kind == "miss"
    fd.note_complete(r, docs=(0,), answer=[3], ttft=0.1, now=0.1)
    assert fd.handle(r, 0.2).kind == "hit_exact"

    fleet = FleetMetrics(router_stats={}, frontdoor_stats=fd.stats())
    fleet.add_replica("replica0", ServingMetrics())
    rep = fleet.format_report()
    assert "front door" in rep and "hit rate 50.00%" in rep
    assert "SLO acme" in rep and "attained 2/2 = 100.00%" in rep
    assert "target 500ms" in rep
    assert "autoscale" in rep
    assert fleet.summary()["frontdoor"]["hit_rate"] == pytest.approx(0.5)
