"""The benchmark's own arithmetic: traffic, work counts, trace reduction.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q
"""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH,
                os.path.join(BENCH, "traffic"), os.path.dirname(os.path.abspath(__file__))]

import generate  # noqa: E402
import model  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402
import trace_reduce as trace  # noqa: E402
import work  # noqa: E402


def real_mix(name):
    return run.load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


# ---- traffic ---------------------------------------------------------------

@pytest.mark.parametrize("name", ["unique.single"])
def test_lengths_are_a_function_of_the_mix(name):
    mix = real_mix(name)
    a = generate.make_passages(mix, 1000, seed=3)
    b = generate.make_passages(mix, 1000, seed=2**33 + 1)
    assert np.array_equal(a.lengths, b.lengths)
    assert a.lengths.max() == mix["max_tokens"]
    assert np.all(a.lengths % mix["token_multiple"] == 0)
    assert 480 <= np.median(a.lengths) <= 540
    assert [len(t) for t in a.tokens] == list(a.lengths)
    assert not np.array_equal(a.vectors, b.vectors)


def prompt_sizes(mix, seed, blocks):
    """Per block of the window's stream: the sorted total prompt lengths."""
    p = generate.make_passages(mix, 512, seed)
    t = generate.Traffic(mix, p, 512, seed)
    t.fill(7)
    s = t.stream()
    k, block = mix["top_k"], mix["shuffle_block"]
    sizes = [int(p.lengths[q.target * k:(q.target + 1) * k].sum()) + len(q.question)
             for q in (next(s) for _ in range(blocks * block))]
    return [sorted(sizes[i:i + block]) for i in range(0, len(sizes), block)], sizes


@pytest.mark.parametrize("targets", ["unique", "zipf"])
def test_every_seed_serves_the_same_sizes_in_another_order(targets):
    mix = dict(real_mix("unique.single"), targets=targets, zipf_s=1.0)
    a, order_a = prompt_sizes(mix, 11, 4)
    b, order_b = prompt_sizes(mix, 2**40 + 3, 4)
    assert a == b
    assert order_a != order_b


def test_retrieval_finds_the_target_neighbourhood():
    """The program's index, built as a run builds it, returns exactly the
    query's neighbourhood."""
    from repro.retrieval.vectordb import IVFIndex
    mix = real_mix("unique.single")
    p = generate.make_passages(mix, 1000, seed=2**34 + 9)
    t = generate.Traffic(mix, p, 1000, seed=2**34 + 9)
    index = IVFIndex(p.vectors, n_clusters=16, nprobe=8)
    s = t.stream()
    k = mix["top_k"]
    for q in t.fill(20) + [next(s) for _ in range(40)]:
        got = sorted(index.search(q.vector, k))
        assert got == list(range(q.target * k, (q.target + 1) * k))


def test_same_seed_same_traffic():
    mix = dict(tiny.TINY_MIX)
    out = []
    for _ in range(2):
        p = generate.make_passages(mix, 512, seed=2**40 + 5)
        t = generate.Traffic(mix, p, 512, seed=2**40 + 5)
        s = t.stream()
        out.append([(q.target, q.question.tolist(), q.vector.tolist())
                    for q in t.fill(3) + [next(s) for _ in range(20)]])
    assert out[0] == out[1]


def test_unique_targets_never_repeat():
    mix = dict(tiny.TINY_MIX, targets="unique", passages=50)
    p = generate.make_passages(mix, 512, seed=9)
    t = generate.Traffic(mix, p, 512, seed=9)
    s = t.stream()
    targets = [q.target for q in t.fill(9)] + [next(s).target for _ in range(16)]
    assert sorted(targets) == list(range(25))
    with pytest.raises(RuntimeError):
        next(s)


def test_zipf_is_skewed_and_fill_takes_the_most_popular():
    mix = dict(tiny.TINY_MIX, passages=4000)
    ranks = generate.target_ranks(mix, 20000)
    counts = np.bincount(ranks, minlength=generate.neighbourhoods(mix))
    top = np.sort(counts)[::-1]
    assert 0.5 < top[:60].sum() / counts.sum() < 0.7   # top 3% of 2000
    assert set(np.argsort(counts)[::-1][:5].tolist()) <= set(range(10))
    p = generate.make_passages(mix, 512, seed=4)
    t = generate.Traffic(mix, p, 512, seed=4)
    assert [q.target for q in t.fill(5)] == list(range(5))


# ---- work counts -----------------------------------------------------------

W = model.Widths(L=2, D=64, F=128, V=512, H=4, KV=2, hd=16, theta=1e4,
                 eps=1e-6, window=0, bias=False, tied=False)


def test_pieces_follow_the_scheduler():
    from repro.serving.scheduler import prefill_piece_sizes
    for segs in ([512, 384, 32], [1000, 7, 0, 33], [40]):
        for chunk in (0, 32, 512):
            assert work.pieces(segs, chunk) == prefill_piece_sizes(segs, chunk)


def test_prefill_attention_counts_by_hand():
    # rows at positions 2, 3, 4 see 3 + 4 + 5 keys
    f, b = work.prefill_attention(W, 2, 3)
    assert f == W.L * 4 * W.H * W.hd * 12
    assert b == W.L * (2 * 3 * W.H * W.hd * 2 + 2 * 5 * W.KV * W.hd * 2)


def test_window_caps_keys():
    w = model.Widths(**{**W.__dict__, "window": 4})
    # positions 2..6 see 3, 4, 4, 4, 4 keys; the kv read is positions 3..6
    f, b = work.prefill_attention(w, 2, 5)
    assert f == w.L * 4 * w.H * w.hd * 19
    assert b == w.L * (2 * 5 * w.H * w.hd * 2 + 2 * 4 * w.KV * w.hd * 2)
    assert work._sum_keys(0, 10000, 4096) == sum(min(p + 1, 4096) for p in range(10000))
    f, _ = work.decode_attention(w, 100)
    assert f == w.L * 4 * w.H * w.hd * 4


def test_count_adds_pieces_and_decode():
    out = work.count(W, [work.Served([48, 8], alpha=16, decoded=2)], chunk=32,
                     peak_flops=1e12, peak_bw=1e11)
    f = sum(work.prefill_attention(W, q, n)[0] for q, n in ((16, 32), (48, 16), (64, 8)))
    assert out["paged_prefill"].flops == f
    d = work.decode_attention(W, 72)[0] + work.decode_attention(W, 73)[0]
    assert out["paged_decode"].flops == d
    mm = W.matmul_flops
    assert out["model_flops"] == f + d + 56 * mm + 2 * mm + 3 * work.head_flops(W)


def test_matmul_flops_match_the_compiled_program():
    """The dense decode step's HLO dots: per row, every layer's matmuls,
    the head, and attention over the whole dense cache."""
    import jax
    import jax.numpy as jnp
    from repro.launch import hlo_analysis
    from repro.models import model as M
    conf = dict(tiny.TINY_CONF, sliding_window=0)
    w = model.widths(conf)
    cfg = run.program_config(conf, model, w)
    params = jax.eval_shape(lambda k: model.make_params(w, k), jax.random.key(0))
    B, S = 3, 64
    cache = M.init_decode_cache(cfg, B, S)
    fn = jax.jit(lambda p, t, c, pos: M.decode_step(cfg, p, t, c, pos))
    text = fn.lower(params, jnp.zeros((B, 1), jnp.int32), cache,
                    jnp.ones((B,), jnp.int32)).compile().as_text()
    hlo = hlo_analysis.analyze(text).flops
    attn = w.L * 4 * w.H * w.hd * S
    want = B * (w.matmul_flops + work.head_flops(w) + attn)
    assert hlo == pytest.approx(want, rel=1e-6)


# ---- trace reduction -------------------------------------------------------

def test_reduce_by_hand():
    ms = 1_000_000
    host = [(0, 100 * ms, trace.WINDOW_SPAN), (10 * ms, 30 * ms, "serve"),
            (30 * ms, 65 * ms, "tables")]
    dev = {"/device:TPU:0": [
        (-5 * ms, 5 * ms, "fusion.1"),                  # half inside
        (5 * ms, 20 * ms, "paged_prefill.3 custom-call"),
        (15 * ms, 25 * ms, "fusion.2"),                 # overlaps the kernel
        (70 * ms, 80 * ms, "x paged_decode"),
    ]}
    r = trace.reduce(dev, host, ("paged_prefill", "paged_decode"))
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.035)     # 0-25 and 70-80
    assert r["kernel_s"]["paged_prefill"] == pytest.approx(0.015)
    assert r["kernel_s"]["paged_decode"] == pytest.approx(0.010)
    gaps = dict(map(tuple, r["breakdown"]["idle_gaps"]))
    assert gaps["tables"] == pytest.approx(0.045)   # 25-70: tables covers 35 of 45
    assert gaps["host (no span)"] == pytest.approx(0.020)
    assert sum(gaps.values()) == pytest.approx(0.065)
    ops = dict(map(tuple, r["breakdown"]["device_ops"]))
    assert ops["fusion.1"] == pytest.approx(0.005)


def test_device_ops_count_leaves_only():
    ms = 1_000_000
    host = [(0, 100 * ms, trace.WINDOW_SPAN)]
    dev = {"/device:TPU:0": [(0, 50 * ms, "%while.4 = loop"), (5 * ms, 10 * ms, "%copy.1"),
                             (20 * ms, 30 * ms, "%paged_prefill.2"), (60 * ms, 70 * ms, "%fusion.3")]}
    r = trace.reduce(dev, host, ("paged_prefill",))
    ops = dict(map(tuple, r["breakdown"]["device_ops"]))
    assert set(ops) == {"%copy.1", "%paged_prefill.2", "%fusion.3"}
    assert r["busy_s"] == pytest.approx(0.06)


def test_reduce_reads_a_recorded_trace(tmp_path):
    """A trace recorded here on the CPU: the window span is found, and a
    trace with no device plane reads as zero busy time."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        jax.jit(lambda a: a @ a)(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    dev, host = trace.read(trace.latest_xplane(str(tmp_path)))
    lo, hi = trace.window_of(host)
    assert hi > lo
    r = trace.reduce(dev, host, run.KERNELS)
    assert r["busy_s"] == 0.0 and r["window_s"] > 0
