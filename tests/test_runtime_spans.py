"""Program spans of the continuous runtime, read back from a profiler trace:
each span appears under its bare name, retrieval once per request, tier
moves inside the commit that causes them, sibling spans apart, and no
compiled program or scope name that a trace reader would take for a
kernel's."""

import os
import re
import sys

import jax
import pytest

from repro.configs import get_reduced
from repro.models import model as M
from repro.retrieval.corpus import make_corpus, make_workload
from repro.retrieval.vectordb import IVFIndex
from repro.serving.config import EngineConfig
from repro.serving.runtime import ContinuousRuntime

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "bench")
)
import trace_reduce  # noqa: E402

KERNEL_NAMES = ("paged_prefill", "paged_decode")
PATH_SPANS = {
    "rt.serve",
    "rt.retrieval",
    "rt.schedule",
    "rt.plan",
    "rt.prefill.pack",
    "rt.prefill.launch",
    "rt.prefill.wait",
    "rt.first_token",
    "rt.commit",
    "rt.tree.demote",
    "rt.decode.pack",
    "rt.decode.launch",
    "rt.decode.wait",
}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A runtime whose device tier holds about two requests, so commits
    demote; every request of its first serve() is traced."""
    cfg = get_reduced("qwen2-0.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    corpus = make_corpus(20, mean_doc_tokens=24, vocab=cfg.vocab_size, seed=0)
    idx = IVFIndex(corpus.doc_vectors, n_clusters=8, nprobe=4)
    wl = make_workload(
        corpus,
        n_requests=5,
        rate=100.0,
        question_tokens=8,
        vocab=cfg.vocab_size,
        zipf_s=1.2,
        seed=1,
    )
    kv_bytes = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.hd * 2
    rt = ContinuousRuntime(
        cfg,
        params,
        corpus,
        idx,
        config=EngineConfig(
            top_k=2,
            gpu_cache_bytes=150 * kv_bytes,
            host_cache_bytes=10**8,
            prefill_chunk=16,
        ),
    )
    where = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(where)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            res = rt.serve(wl, max_new_tokens=2)
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.latest_xplane(where)
    _, host = trace_reduce.read(path)
    spans = sorted(h for h in host if h[2].startswith("rt."))
    return rt, res, spans, path


def _of(spans, name):
    return [(s, e) for s, e, n in spans if n == name]


def test_every_span_of_the_path_appears_under_its_bare_name(traced):
    rt, _, spans, _ = traced
    assert rt.tree.stats["gpu_evictions"] > 0
    names = {n for _, _, n in spans}
    assert PATH_SPANS <= names, PATH_SPANS - names
    assert all(re.fullmatch(r"rt(\.[a-z_]+)+", n) for n in names), names


def test_one_retrieval_span_per_request_with_its_id(traced):
    _, res, spans, path = traced
    assert len(_of(spans, "rt.retrieval")) == len(res)
    from jax.profiler import ProfileData

    ids = [
        v
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for e in line.events
        if e.name == "rt.retrieval"
        for k, v in e.stats
        if k == "req_id"
    ]
    assert sorted(ids) == sorted(r.req_id for r in res)


def _inside(inner, outer):
    return all(any(os <= s and e <= oe for os, oe in outer) for s, e in inner)


def test_demotion_nests_inside_the_commit(traced):
    _, _, spans, _ = traced
    demotes = _of(spans, "rt.tree.demote")
    assert demotes
    assert _inside(demotes, _of(spans, "rt.commit"))


def test_every_span_nests_inside_serve(traced):
    _, _, spans, _ = traced
    serves = _of(spans, "rt.serve")
    assert len(serves) == 1
    assert _inside([(s, e) for s, e, n in spans], serves)


def test_sibling_spans_do_not_overlap(traced):
    _, _, spans, _ = traced
    siblings = [
        "rt.retrieval",
        "rt.schedule",
        "rt.plan",
        "rt.prefill.pack",
        "rt.prefill.launch",
        "rt.prefill.wait",
        "rt.first_token",
        "rt.commit",
        "rt.decode.pack",
        "rt.decode.launch",
        "rt.decode.wait",
    ]
    ivs = sorted((s, e) for s, e, n in spans if n in siblings)
    for (_, e0), (s1, _) in zip(ivs, ivs[1:]):
        assert e0 <= s1


def test_host_seconds_hold_the_traced_names(traced):
    rt, _, spans, _ = traced
    secs = rt.metrics.host_seconds
    assert set(secs) == {n for _, _, n in spans}
    assert all(v > 0 for v in secs.values())
    assert secs["rt.commit"] >= secs["rt.tree.demote"]
    assert "host seconds by span" in rt.metrics.format_report()


def test_no_program_name_holds_a_kernel_name(traced):
    """A trace reader credits a kernel with every device op whose text
    holds the kernel's name, so no jitted step or scope may carry one."""
    rt, _, _, _ = traced
    hlo = rt.compiled_steps()
    assert "decode" in hlo and any(k.startswith("prefill") for k in hlo)
    for name, text in hlo.items():
        op_names = re.findall(r'op_name="([^"]*)"', text)
        assert any("rt_" in o for o in op_names), name
        assert any("/attn/" in o for o in op_names), name
        for line in text.splitlines():
            if "custom-call" in line and "tpu_custom_call" in line:
                continue  # the Pallas kernel itself carries its name
            for o in re.findall(r'op_name="([^"]*)"', line):
                assert not any(k in o for k in KERNEL_NAMES), (name, o)
