"""Paged ragged prefill: parity of the kernel implementations (Pallas in
interpret mode, per-page jnp online softmax) against the dense oracles
(``ref.reference_paged_prefill`` and ``ref.reference_prefix_attention``),
the ragged edge cases the shape sweep misses (length-0 chunks, mid-block
unaligned cached tails, GQA R in {1, 2, 4}, sliding windows, logit softcap),
two hypothesis properties — block-table permutation invariance and
any-chunk-split row identity (the foundation of the engine's token-identity
guarantee) — the ``prefix_attention`` fast-path pin, and the model/runtime
integration: ``paged_prefill_step`` reproduces dense ``prefill`` logits
bit-for-bit without ever materializing the dense (L, B, S, KV, hd) context.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import paged_prefill as pp
from repro.kernels import prefix_attention as pa
from repro.models import model as M

KEY = jax.random.PRNGKey(7)

IMPLS = ("interpret", "jnp")


def _random_case(key, B, H, KV, hd, page, n_pages, n_slots,
                 dtype=jnp.float32, Sq=8):
    """Arbitrary run tables (counts in [0, page], positions contiguous in
    run order) with the query span covering the FINAL Sq positions of each
    request — the mid-prefill shape: everything before q_start is cached."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    q = jax.random.normal(k1, (B, H, Sq, hd), dtype)
    kp = jax.random.normal(k2, (3, n_pages, KV, page, hd), dtype)
    vp = jax.random.normal(k3, (3, n_pages, KV, page, hd), dtype)
    tables = jax.random.randint(k4, (B, n_slots), 0, n_pages)
    counts = jax.random.randint(k5, (B, n_slots), 0, page + 1)
    starts = jnp.concatenate([jnp.zeros((B, 1), jnp.int32),
                              jnp.cumsum(counts, axis=1)[:, :-1]], axis=1)
    total = counts.sum(axis=1)
    q_len = jnp.minimum(total, Sq).astype(jnp.int32)
    q_start = (total - q_len).astype(jnp.int32)
    return q, kp, vp, tables, counts.astype(jnp.int32), starts, q_start, q_len


def _scatter_sequence(key, T, KV, hd, page, n_pages, order=None, layer=1):
    """Place one logical (T, KV, hd) KV sequence into physical pages (run
    order = ``order``, full pages except the final tail) and return the pool
    planes + the (1, n_slots) run table addressing it.  Non-target layers
    and unused pages hold garbage — reading them is a bug."""
    k1, k2, k3 = jax.random.split(key, 3)
    kseq = jax.random.normal(k1, (T, KV, hd))
    vseq = jax.random.normal(k2, (T, KV, hd))
    nb = -(-T // page)
    if order is None:
        order = list(range(1, nb + 1))
    kp = jax.random.normal(k3, (3, n_pages, KV, page, hd))
    vp = kp * -0.7 + 1.3
    counts = np.zeros(nb, np.int32)
    for i, pid in enumerate(order[:nb]):
        c = min(page, T - i * page)
        kp = kp.at[layer, pid, :, :c].set(
            kseq[i * page:i * page + c].swapaxes(0, 1))
        vp = vp.at[layer, pid, :, :c].set(
            vseq[i * page:i * page + c].swapaxes(0, 1))
        counts[i] = c
    tables = jnp.asarray([order[:nb]], jnp.int32)
    counts = jnp.asarray(counts[None])
    starts = jnp.concatenate([jnp.zeros((1, 1), jnp.int32),
                              jnp.cumsum(counts, axis=1)[:, :-1]], axis=1)
    return kseq, vseq, kp, vp, tables, counts, starts


# ---------------------------------------------------------------------------
# kernel-level parity (kernels CI lane)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,KV,hd,page,n_slots,Sq", [
    (2, 4, 2, 32, 8, 4, 8),       # GQA R=2
    (1, 8, 2, 64, 16, 3, 16),     # GQA R=4
    (3, 4, 4, 128, 8, 6, 8),      # MHA
    (2, 6, 1, 32, 8, 5, 24),      # MQA, multi-q-block at block_q=8
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.slow
def test_paged_prefill_parity_sweep(B, H, KV, hd, page, n_slots, Sq, dtype):
    """Interpret-mode kernel and jnp path agree with the dense oracle on the
    layer-major layout, including runs that end mid-slot (counts < page)."""
    q, kp, vp, tables, counts, starts, q_start, q_len = _random_case(
        jax.random.fold_in(KEY, B * H + hd + Sq), B, H, KV, hd, page, 16,
        n_slots, dtype, Sq=Sq)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    for layer in (0, 2):
        want = ref.reference_paged_prefill(q, kp, vp, tables, counts, starts,
                                           q_start, q_len, layer)
        for impl in IMPLS:
            got = ops.paged_prefill_attention(
                q, kp, vp, tables, counts, starts, q_start, q_len,
                jnp.int32(layer), jnp.int32(0), impl=impl)
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32),
                                       atol=tol, err_msg=f"{impl}/L{layer}")
        # multi-q-block grid (block_q < Sq) through the kernel directly
        got = pp.paged_prefill_attention(
            q, kp, vp, tables, counts, starts, q_start, q_len,
            jnp.int32(layer), jnp.int32(0), block_q=8, interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, err_msg=f"block_q=8/L{layer}")


@pytest.mark.parametrize("R", [1, 2, 4])
@pytest.mark.parametrize("window,cap", [(0, 0.0), (7, 0.0), (0, 30.0),
                                        (5, 30.0)])
@pytest.mark.slow
def test_matches_reference_prefix_attention(R, window, cap):
    """Against the ORIGINAL dense oracle: a contiguous [cached prefix ‖ new]
    sequence scattered into pages (unaligned tail included) must reproduce
    ``reference_prefix_attention`` for every GQA ratio, window, softcap."""
    H, hd, page = 4, 32, 8
    KV = H // R
    T, new = 29, 11                       # 29 % 8 != 0: mid-block tail
    layer = 1
    kseq, vseq, kp, vp, tables, counts, starts = _scatter_sequence(
        jax.random.fold_in(KEY, 13 * R + window), T, KV, hd, page, 12,
        layer=layer)
    q = jax.random.normal(jax.random.fold_in(KEY, R), (1, H, new, hd))
    want = ref.reference_prefix_attention(
        q, kseq.transpose(1, 0, 2)[None], vseq.transpose(1, 0, 2)[None],
        prefix_len=T - new, window=window, logit_cap=cap)
    q_start = jnp.asarray([T - new], jnp.int32)
    q_len = jnp.asarray([new], jnp.int32)
    for impl in IMPLS:
        got = ops.paged_prefill_attention(
            q, kp, vp, tables, counts, starts, q_start, q_len,
            jnp.int32(layer), jnp.int32(window), logit_cap=cap, impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, err_msg=impl)


@pytest.mark.slow
def test_midblock_unaligned_cached_tails():
    """Cached doc tails ending mid-block (counts < page on non-final runs)
    shift every later absolute position — the exact case a page-aligned
    assumption breaks.  Gather the runs densely and compare."""
    page, KV, H, hd = 8, 2, 4, 32
    kp = jax.random.normal(jax.random.fold_in(KEY, 1), (3, 16, KV, page, hd))
    vp = jax.random.normal(jax.random.fold_in(KEY, 2), kp.shape)
    tables = jnp.asarray([[3, 7, 1, 9], [5, 5, 0, 0]], jnp.int32)
    counts = jnp.asarray([[5, 3, 8, 2], [8, 6, 0, 0]], jnp.int32)
    starts = jnp.concatenate([jnp.zeros((2, 1), jnp.int32),
                              jnp.cumsum(counts, axis=1)[:, :-1]], axis=1)
    layer, Sq = 1, 8
    total = counts.sum(axis=1)
    q_len = jnp.asarray([Sq, 6], jnp.int32)
    q_start = total - q_len
    q = jax.random.normal(jax.random.fold_in(KEY, 3), (2, H, Sq, hd))
    want = ref.reference_paged_prefill(q, kp, vp, tables, counts, starts,
                                       q_start, q_len, layer)
    # cross-check the oracle against the dense prefix reference per request
    for b in range(2):
        t = int(total[b])
        dk = np.zeros((t, KV, hd), np.float32)
        dv = np.zeros_like(dk)
        for j in range(tables.shape[1]):
            c, s0 = int(counts[b, j]), int(starts[b, j])
            pid = int(tables[b, j])
            dk[s0:s0 + c] = np.asarray(kp)[layer, pid, :, :c].swapaxes(0, 1)
            dv[s0:s0 + c] = np.asarray(vp)[layer, pid, :, :c].swapaxes(0, 1)
        n = int(q_len[b])
        dense = ref.reference_prefix_attention(
            q[b:b + 1, :, :n], jnp.asarray(dk.transpose(1, 0, 2))[None],
            jnp.asarray(dv.transpose(1, 0, 2))[None], prefix_len=t - n)
        np.testing.assert_allclose(np.asarray(want[b:b + 1, :, :n]),
                                   np.asarray(dense), atol=1e-4)
    for impl in IMPLS:
        got = ops.paged_prefill_attention(q, kp, vp, tables, counts, starts,
                                          q_start, q_len, jnp.int32(layer),
                                          jnp.int32(0), impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, err_msg=impl)


@pytest.mark.slow
def test_length_zero_chunks_produce_zero_not_nan():
    """q_len == 0 rows (ragged-batch padding slots) must return exactly 0 —
    not NaN, not an average of whatever garbage the scratch page holds —
    and rows past q_len of a live request must be exactly 0 too."""
    q, kp, vp, tables, counts, starts, q_start, q_len = _random_case(
        KEY, 3, 4, 2, 32, 8, 16, 4, Sq=8)
    q_len = jnp.asarray([8, 0, 5], jnp.int32)
    q_start = jnp.maximum(counts.sum(axis=1) - q_len, 0)
    for impl in IMPLS:
        out = np.asarray(ops.paged_prefill_attention(
            q, kp, vp, tables, counts, starts, q_start, q_len,
            jnp.int32(0), jnp.int32(0), impl=impl), np.float32)
        assert np.isfinite(out).all(), impl
        assert np.abs(out[1]).max() == 0.0, impl           # whole dead row
        assert np.abs(out[2, :, 5:]).max() == 0.0, impl    # ragged tail
        assert np.abs(out[0]).max() > 0.0, impl
        assert np.abs(out[2, :, :5]).max() > 0.0, impl


# ---------------------------------------------------------------------------
# hypothesis properties
# ---------------------------------------------------------------------------

_PERM = dict(H=4, KV=2, hd=16, page=8, n_pages=12, T=22, new=9)


def _check_permutation_invariance(order):
    """Same logical sequence, ANY physical page placement: kernel == oracle
    == dense prefix reference (the paged layout is pure storage)."""
    s = _PERM
    kseq, vseq, kp, vp, tables, counts, starts = _scatter_sequence(
        KEY, s["T"], s["KV"], s["hd"], s["page"], s["n_pages"], order=order)
    q = jax.random.normal(jax.random.fold_in(KEY, 4),
                          (1, s["H"], s["new"], s["hd"]))
    q_start = jnp.asarray([s["T"] - s["new"]], jnp.int32)
    q_len = jnp.asarray([s["new"]], jnp.int32)
    dense = ref.reference_prefix_attention(
        q, kseq.transpose(1, 0, 2)[None], vseq.transpose(1, 0, 2)[None],
        prefix_len=s["T"] - s["new"])
    for impl in IMPLS:
        got = ops.paged_prefill_attention(
            q, kp, vp, tables, counts, starts, q_start, q_len,
            jnp.int32(1), jnp.int32(0), impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=1e-4, err_msg=impl)


@pytest.mark.slow
def test_block_table_permutation_spot_checks():
    _check_permutation_invariance(None)            # identity-ish placement
    _check_permutation_invariance([7, 3, 11])
    _check_permutation_invariance([11, 0, 5])


@pytest.mark.slow
def test_hypothesis_block_table_permutation_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=20, deadline=None)
    @given(perm=st.permutations(range(_PERM["n_pages"])))
    def check(perm):
        _check_permutation_invariance(list(perm))

    check()


def _check_chunk_split_identity(cuts, impl):
    """With the KV fully resident, computing the query span in ANY sequence
    of chunks yields the row outputs of the one-shot call: each row's
    online softmax walks the same slots in the same order whatever chunk it
    rides in.  Equality is to f32 ULP (XLA may retile the q·k matmul per Sq
    shape); BITWISE logits identity under chunking is asserted at the model
    level below, where bf16 activations absorb the ULP wobble — that is the
    kernel half of the engine's any-chunk-size token-identity guarantee."""
    s = _PERM
    _, _, kp, vp, tables, counts, starts = _scatter_sequence(
        KEY, s["T"], s["KV"], s["hd"], s["page"], s["n_pages"])
    new = s["new"]
    q = jax.random.normal(jax.random.fold_in(KEY, 5),
                          (1, s["H"], new, s["hd"]))
    q0 = s["T"] - new
    one = ops.paged_prefill_attention(
        q, kp, vp, tables, counts, starts, jnp.asarray([q0], jnp.int32),
        jnp.asarray([new], jnp.int32), jnp.int32(1), jnp.int32(0), impl=impl)
    bounds = [0] + sorted(cuts) + [new]
    pieces = []
    for a, b in zip(bounds, bounds[1:]):
        if a == b:
            continue
        pieces.append(ops.paged_prefill_attention(
            q[:, :, a:b], kp, vp, tables, counts, starts,
            jnp.asarray([q0 + a], jnp.int32), jnp.asarray([b - a], jnp.int32),
            jnp.int32(1), jnp.int32(0), impl=impl))
    got = jnp.concatenate(pieces, axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(one),
                               atol=2e-6, err_msg=impl)


@pytest.mark.slow
def test_chunk_split_identity_spot_checks():
    for impl in IMPLS:
        _check_chunk_split_identity([4], impl)
        _check_chunk_split_identity([1, 2, 3, 8], impl)
        _check_chunk_split_identity(list(range(1, _PERM["new"])), impl)


@pytest.mark.slow
def test_hypothesis_any_chunk_split_identity_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=15, deadline=None)
    @given(cuts=st.sets(st.integers(1, _PERM["new"] - 1), max_size=5))
    def check(cuts):
        _check_chunk_split_identity(sorted(cuts), "jnp")

    check()


# ---------------------------------------------------------------------------
# prefix_attention fast path (the dense A/B baseline)
# ---------------------------------------------------------------------------


class _FakeRef:
    def __init__(self, a):
        self.a = a

    def __getitem__(self, idx):
        return self.a

    def __setitem__(self, idx, val):
        self.a = val


@pytest.mark.slow
def test_prefix_fastpath_branches_bitwise_equivalent():
    """The ``pl.when`` fast path on fully-visible kv blocks skips the
    iota/compare/select; pin that the masked branch with an all-True mask
    performs the BITWISE-identical accumulator update (``jnp.where(True, s,
    NEG_INF)`` must return ``s`` unchanged), so the fast path can never
    change results — only skip work."""
    k1, k2 = jax.random.split(KEY)
    s = jax.random.normal(k1, (8, 8), jnp.float32) * 4.0
    v = jax.random.normal(k2, (8, 32), jnp.float32)
    mask = jnp.ones_like(s, bool)
    states = []
    for scores in (s, jnp.where(mask, s, pa.NEG_INF)):
        acc = _FakeRef(jnp.ones((8, 32), jnp.float32))
        m = _FakeRef(jnp.full((8,), -1.0, jnp.float32))
        el = _FakeRef(jnp.full((8,), 2.0, jnp.float32))
        pa._accumulate(scores, v, acc, m, el)
        states.append((acc.a, m.a, el.a))
    for got, want in zip(states[0], states[1]):
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("window", [0, 100])
@pytest.mark.slow
def test_prefix_flash_attention_fastpath_parity(window):
    """End to end through the rewritten kernel: a prefix-heavy shape where
    whole kv blocks take the fast path (prefix_len covers multiple full
    block_k tiles) still matches the dense oracle, and the deprecated
    ``prefix_attention`` wrapper forwards bit-for-bit."""
    B, H, KV, hd = 1, 4, 2, 32
    Sq, prefix = 24, 80
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, window), 3)
    q = jax.random.normal(k1, (B, H, Sq, hd))
    k = jax.random.normal(k2, (B, KV, prefix + Sq, hd))
    v = jax.random.normal(k3, k.shape)
    got = pa.prefix_flash_attention(q, k, v, prefix_len=prefix,
                                    window=window, block_q=8, block_k=16,
                                    interpret=True)
    want = ref.reference_prefix_attention(q, k, v, prefix_len=prefix,
                                          window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    legacy = pa.prefix_attention(q, k, v, prefix_len=prefix, window=window,
                                 block_q=8, block_k=16, interpret=True)
    assert np.array_equal(np.asarray(legacy), np.asarray(got))


# ---------------------------------------------------------------------------
# model + runtime integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving_setup():
    from repro.configs import get_reduced
    from repro.retrieval.corpus import make_corpus, make_workload
    from repro.retrieval.vectordb import IVFIndex
    cfg = get_reduced("qwen2-0.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    corpus = make_corpus(16, mean_doc_tokens=22, vocab=cfg.vocab_size, seed=0)
    idx = IVFIndex(corpus.doc_vectors, n_clusters=8, nprobe=4)
    wl = make_workload(corpus, n_requests=6, rate=100.0, question_tokens=8,
                       vocab=cfg.vocab_size, zipf_s=1.2, seed=1)
    return cfg, params, corpus, idx, wl


def _alloc_plan(cfg, n_tokens, bs, n_blocks, rng):
    """Random page placement for a fresh sequence: per-token write coords +
    the run table addressing them (one run per block, runs start at slot 0)."""
    nb = -(-n_tokens // bs)
    blocks = rng.permutation(n_blocks - 1)[:nb] + 1
    pos = np.arange(n_tokens)
    wblk = blocks[pos // bs].astype(np.int32)
    wslot = (pos % bs).astype(np.int32)
    T = nb + 2
    tables = np.zeros((1, T), np.int32)
    counts = np.zeros((1, T), np.int32)
    starts = np.zeros((1, T), np.int32)
    tables[0, :nb] = blocks
    counts[0, :nb] = [min(bs, n_tokens - i * bs) for i in range(nb)]
    starts[0, :nb] = np.arange(nb) * bs
    return wblk, wslot, tables, counts, starts


# Chunk rows for the page-write parity test: (block, bucket, segments, rows).
# A row is (s0, [t0, t1, ...]): t0 tokens resume a segment at slot s0, each
# later segment starts a fresh page; None is a padding row.
WRITE_CASES = {
    "mid_page": (8, 8, 1, [(5, [3])]),
    # 32 tokens from slot 7: five pages, the bound, and no padding token
    "crosses_pages": (8, 32, 1, [(7, [32])]),
    # three segments at the bound's largest run count: 2 + 3 + 2 pages
    "segments": (8, 32, 3, [(7, [2, 17, 13])]),
    "padding": (8, 16, 2, [(0, [10]), None, (3, [4, 5])]),
    "short_bucket": (16, 8, 1, [(13, [8]), (0, [5])]),
}


def _write_rows(block, Sq, rows, n_blocks, rng, scratch=0):
    """(B, Sq) write coords for chunk rows on fresh random pages; padding
    tokens and padding rows aim at the scratch block."""
    free = list(rng.permutation([b for b in range(n_blocks) if b != scratch]))
    wblk = np.full((len(rows), Sq), scratch, np.int32)
    wslot = np.zeros((len(rows), Sq), np.int32)
    q_len = np.zeros(len(rows), np.int32)
    for b, row in enumerate(rows):
        if row is None:
            continue
        s0, lens = row
        i = 0
        for g, t in enumerate(lens):
            pos = np.arange(t) + (s0 if g == 0 else 0)
            pages = [free.pop() for _ in range(-(-(pos[-1] + 1) // block))]
            wblk[b, i:i + t] = np.asarray(pages)[pos // block]
            wslot[b, i:i + t] = pos % block
            i += t
        q_len[b] = i
    return wblk, wslot, q_len


@pytest.mark.parametrize("case", list(WRITE_CASES))
def test_write_pages_matches_token_scatter(case):
    """The page-granular KV write leaves every page but the scratch block
    bit-identical to the token scatter it replaced, at the static run count
    ``max_write_runs`` gives the bucket — which each row respects."""
    from repro.kvcache.paged import max_write_runs, run_starts, write_pages
    block, Sq, segments, rows = WRITE_CASES[case]
    L_, n_blocks, KV, hd, li = 3, 24, 2, 4, 1
    rng = np.random.default_rng(len(case))
    wblk, wslot, q_len = _write_rows(block, Sq, rows, n_blocks, rng)
    n_runs = max_write_runs(Sq, block, segments)
    runs = run_starts(wblk, wslot, np.arange(Sq)[None] < q_len[:, None])
    assert runs.sum(axis=1).max() <= n_runs
    if case in ("crosses_pages", "segments"):
        assert runs.sum(axis=1).max() == n_runs
    pages = jnp.asarray(rng.standard_normal((L_, n_blocks, KV, block, hd)),
                        jnp.bfloat16)
    vals = jnp.asarray(rng.standard_normal((len(rows), Sq, KV, hd)))
    got = jax.jit(write_pages, static_argnums=6)(
        pages, jnp.int32(li), jnp.asarray(wblk), jnp.asarray(wslot),
        jnp.asarray(q_len), vals, n_runs)
    # the oracle: the token scatter, padding tokens (scratch block) included
    want = pages.at[li, wblk, :, wslot].set(vals.astype(pages.dtype))
    live = np.arange(1, n_blocks)                  # block 0 is the scratch
    assert np.array_equal(np.asarray(got)[:, live], np.asarray(want)[:, live])


def test_run_paged_rows_asserts_rows_past_the_run_bound(serving_setup):
    """A row that would write more pages than the step's static run count
    trips the host assert before anything runs — it is never dropped."""
    from repro.kvcache.paged import max_write_runs
    from repro.serving.config import EngineConfig
    from repro.serving.runtime import ContinuousRuntime
    cfg, params, corpus, idx, _ = serving_setup
    rt = ContinuousRuntime(cfg, params, corpus, idx, n_blocks=32,
                           config=EngineConfig(top_k=2, attn="paged",
                                               prefill_chunk=8))
    n, bs = 8, rt.store.block_size
    assert max_write_runs(n, bs, rt._row_segments) < n
    wblk = np.arange(1, n + 1, dtype=np.int32)     # one page a token
    wslot = np.full(n, bs - 1, np.int32)
    T = 4
    row = (None, np.zeros(n, np.int32), wblk, wslot, 0,
           np.zeros(T, np.int32), np.zeros(T, np.int32),
           np.zeros(T, np.int32), n)
    with pytest.raises(AssertionError, match="writes 8 pages"):
        rt._run_paged_rows([row])


def test_paged_prefill_step_matches_dense_prefill(serving_setup):
    """paged_prefill_step == dense prefill logits BIT-FOR-BIT through the
    real model (rope, GQA, per-layer windows, scan), one-shot and split
    into chunks — the engine-level token-identity contract in miniature."""
    cfg, params, _, _, _ = serving_setup
    rng = np.random.default_rng(3)
    n_tokens, bs, n_blocks = 23, 8, 32
    toks = rng.integers(0, cfg.vocab_size, size=(1, n_tokens)).astype(np.int32)
    want_logits, _ = M.prefill(cfg, params, {"tokens": jnp.asarray(toks)})
    want = np.asarray(want_logits[:, -1:])

    wblk, wslot, tables, counts, starts = _alloc_plan(
        cfg, n_tokens, bs, n_blocks, rng)
    kp = jnp.zeros((cfg.n_layers, n_blocks, cfg.n_kv_heads, bs, cfg.hd),
                   cfg.jdtype)
    vp = jnp.zeros_like(kp)
    got, kp1, vp1 = M.paged_prefill_step(
        cfg, params, jnp.asarray(toks), kp, vp, jnp.asarray(tables),
        jnp.asarray(counts), jnp.asarray(starts),
        jnp.zeros((1,), jnp.int32), jnp.asarray([n_tokens], jnp.int32),
        jnp.asarray(wblk[None]), jnp.asarray(wslot[None]), attn_impl="jnp")
    assert np.array_equal(np.asarray(got), want)

    # chunked: same table, two calls threading the pool — still bitwise
    kp2, vp2 = jnp.zeros_like(kp), jnp.zeros_like(vp)
    cut = 9
    for a, b in ((0, cut), (cut, n_tokens)):
        got, kp2, vp2 = M.paged_prefill_step(
            cfg, params, jnp.asarray(toks[:, a:b]), kp2, vp2,
            jnp.asarray(tables), jnp.asarray(counts), jnp.asarray(starts),
            jnp.asarray([a], jnp.int32), jnp.asarray([b - a], jnp.int32),
            jnp.asarray(wblk[None, a:b]), jnp.asarray(wslot[None, a:b]),
            attn_impl="jnp")
    assert np.array_equal(np.asarray(got), want)
    # the scattered KV is identical too: chunking changes no pool byte
    assert np.array_equal(np.asarray(kp1), np.asarray(kp2))
    assert np.array_equal(np.asarray(vp1), np.asarray(vp2))


def test_paged_prefill_never_materializes_dense_context(serving_setup):
    """jaxpr regression: no intermediate of the paged prefill step may reach
    the dense-gather footprint L*B*S*KV*hd the retired concat path paid —
    the pool planes threaded through unchanged are the one exemption."""
    cfg, params, corpus, idx, wl = serving_setup
    from repro.serving.runtime import ContinuousRuntime
    from repro.serving.config import EngineConfig
    rt = ContinuousRuntime(cfg, params, corpus, idx, n_blocks=64,
                           config=EngineConfig(top_k=2, attn="paged"))
    rt.max_new_tokens = 4
    max_ctx = 2 * int(max(corpus.doc_lengths)) + 16
    n_slots = rt.store.pool.blocks_for_tokens(max_ctx) + 1
    S = n_slots * rt.store.block_size
    B, Sq = rt.sched.config.max_prefill_bs, 16
    dense_elems = cfg.n_layers * B * S * cfg.n_kv_heads * cfg.hd
    pool_elems = int(np.prod(rt.store.k.shape))
    T = n_slots + rt.top_k + 1
    jaxpr = jax.make_jaxpr(
        lambda p, toks, tb, ct, st_, qs, ql, wb, ws, kp, vp:
        M.paged_prefill_step(cfg, p, toks, kp, vp, tb, ct, st_, qs, ql,
                             wb, ws, attn_impl="jnp"))(
        params, jnp.zeros((B, Sq), jnp.int32),
        jnp.zeros((B, T), jnp.int32), jnp.zeros((B, T), jnp.int32),
        jnp.zeros((B, T), jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.ones((B,), jnp.int32), jnp.zeros((B, Sq), jnp.int32),
        jnp.zeros((B, Sq), jnp.int32), rt.store.k, rt.store.v)

    def max_interm(jpr):
        worst = 0
        for eqn in jpr.eqns:
            for val in eqn.params.values():
                for v in (val if isinstance(val, (list, tuple)) else [val]):
                    if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                        worst = max(worst, max_interm(v.jaxpr))
                    elif hasattr(v, "eqns"):
                        worst = max(worst, max_interm(v))
            for var in eqn.outvars:
                sz = int(np.prod(var.aval.shape)) if var.aval.shape else 1
                if sz != pool_elems:      # threaded pool planes are fine
                    worst = max(worst, sz)
        return worst

    worst = max_interm(jaxpr.jaxpr)
    assert worst < dense_elems, (worst, dense_elems)


def test_runtime_paged_prefill_tokens_match_dense(serving_setup):
    """e2e: the paged engine's chunked ragged prefill reproduces the dense
    engine's greedy tokens, batches real rows with ragged q_len, reuses hit
    pages in place (hit_runs populated on cache hits), and leaks nothing."""
    from repro.serving.runtime import ContinuousRuntime
    cfg, params, corpus, idx, wl = serving_setup
    seen = {"rows": 0, "ragged": 0, "hit_runs": 0}
    from repro.serving.config import EngineConfig
    rt = ContinuousRuntime(cfg, params, corpus, idx,
                           config=EngineConfig(top_k=2, attn="paged",
                                               prefill_chunk=6))
    orig = rt._run_paged_rows

    def spy(rows):
        seen["rows"] += len(rows)
        lens = {r[-1] for r in rows}
        if len(lens) > 1:
            seen["ragged"] += 1
        for r in rows:
            seen["hit_runs"] += len(r[0].cs.hit_runs)
        return orig(rows)

    rt._run_paged_rows = spy
    res_p = rt.serve(wl, max_new_tokens=4)
    rt_d = ContinuousRuntime(cfg, params, corpus, idx,
                             config=EngineConfig(top_k=2, attn="dense",
                                                 prefill_chunk=6))
    res_d = rt_d.serve(wl, max_new_tokens=4)
    assert [r.tokens for r in res_p] == [r.tokens for r in res_d]
    assert seen["rows"] > 0
    assert seen["hit_runs"] > 0, "expected cache hits to be read in place"
    rt.tree.check_invariants()
    rt.store.pool.check()
    # leak freedom: every pool block is owned by the tree (plus the scratch
    # block) once all requests retire
    tree_blocks = sum(len(n.payload_gpu.blocks) for n in rt.tree.nodes()
                      if n.in_gpu and n.payload_gpu is not None)
    live = rt.store.pool.n_blocks - rt.store.pool.free_blocks
    assert live == tree_blocks + 1
