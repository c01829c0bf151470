"""Paged decode attention: parity of the three implementations (Pallas
kernel in interpret mode, per-page jnp online softmax, dense oracle) on the
store's layer-major layout, the slot-mapping edge cases the shape sweep in
test_kernels.py misses (length-0 rows, mid-slot shared tails, GQA R > 1,
sliding windows, logit softcap), the run-table packing contract, a
hypothesis permutation property against the dense ``decode_step`` attention,
and the e2e guarantee: ``attn="paged"`` reproduces the dense engine's greedy
tokens without ever materializing the dense (L, B, S, KV, hd) context.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kvcache.paged import gather_slots
from repro.models import layers as L
from repro.models import model as M

KEY = jax.random.PRNGKey(0)


def _runs_to_dense(kp, vp, tables, counts, layer):
    """Gather the logical sequences out of the page planes: (B, Smax, KV, hd)
    dense caches + (B,) lengths, padding rows to the longest request."""
    B, n_slots = tables.shape
    KV, page, hd = kp.shape[2:]
    lengths = np.asarray(counts.sum(axis=1))
    smax = max(int(lengths.max()), 1)
    dk = np.zeros((B, smax, KV, hd), np.asarray(kp).dtype)
    dv = np.zeros_like(dk)
    for b in range(B):
        t = 0
        for j in range(n_slots):
            c = int(counts[b, j])
            pid = int(tables[b, j])
            dk[b, t:t + c] = np.asarray(kp)[layer, pid, :, :c].swapaxes(0, 1)
            dv[b, t:t + c] = np.asarray(vp)[layer, pid, :, :c].swapaxes(0, 1)
            t += c
    return jnp.asarray(dk), jnp.asarray(dv), jnp.asarray(lengths, jnp.int32)


def _random_case(key, B, H, KV, hd, page, n_pages, n_slots, dtype=jnp.float32):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    q = jax.random.normal(k1, (B, H, hd), dtype)
    kp = jax.random.normal(k2, (3, n_pages, KV, page, hd), dtype)
    vp = jax.random.normal(k3, (3, n_pages, KV, page, hd), dtype)
    tables = jax.random.randint(k4, (B, n_slots), 0, n_pages)
    counts = jax.random.randint(k5, (B, n_slots), 0, page + 1)
    starts = jnp.concatenate([jnp.zeros((B, 1), jnp.int32),
                              jnp.cumsum(counts, axis=1)[:, :-1]], axis=1)
    qpos = counts.sum(axis=1) - 1
    return q, kp, vp, tables, counts.astype(jnp.int32), starts, qpos


@pytest.mark.parametrize("B,H,KV,hd,page,n_slots", [
    (2, 4, 2, 32, 8, 4),       # GQA R=2
    (1, 8, 2, 64, 16, 3),      # GQA R=4
    (3, 4, 4, 128, 8, 6),      # MHA
    (2, 6, 1, 32, 8, 5),       # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.slow
def test_paged_decode_parity_sweep(B, H, KV, hd, page, n_slots, dtype):
    """Interpret-mode kernel and jnp path agree with the dense oracle on the
    layer-major layout, including runs that end mid-slot (counts < page)."""
    q, kp, vp, tables, counts, starts, qpos = _random_case(
        jax.random.fold_in(KEY, B * H + hd), B, H, KV, hd, page, 16, n_slots,
        dtype)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    for layer in (0, 2):
        want = ref.reference_paged_decode(q, kp, vp, tables, counts, starts,
                                          qpos, layer)
        for impl in ("interpret", "jnp"):
            got = ops.paged_decode_attention(
                q, kp, vp, tables, counts, starts, qpos,
                jnp.int32(layer), jnp.int32(0), impl=impl)
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32),
                                       atol=tol, err_msg=f"{impl}/L{layer}")


@pytest.mark.slow
def test_matches_dense_decode_attention_with_midslot_tail():
    """A request whose last live token sits mid-slot in a shared unaligned
    tail block (counts < page on the FINAL run too) must agree with the
    model's dense ``decode_attention`` over the gathered sequence."""
    q, kp, vp, _, _, _, _ = _random_case(KEY, 2, 4, 2, 32, 8, 16, 4)
    tables = jnp.asarray([[3, 7, 1, 9], [5, 5, 0, 0]], jnp.int32)
    # row 0: two unaligned doc tails (5, 3) then a full page then a 2-token
    # tail; row 1: one page reused twice (refcount-shared) + empty runs
    counts = jnp.asarray([[5, 3, 8, 2], [8, 8, 0, 0]], jnp.int32)
    starts = jnp.concatenate([jnp.zeros((2, 1), jnp.int32),
                              jnp.cumsum(counts, axis=1)[:, :-1]], axis=1)
    qpos = counts.sum(axis=1) - 1
    layer = 1
    dk, dv, lengths = _runs_to_dense(kp, vp, tables, counts, layer)
    want = L.decode_attention(q[:, None], dk, dv, pos=lengths)[:, 0]
    for impl in ("interpret", "jnp"):
        got = ops.paged_decode_attention(q, kp, vp, tables, counts, starts,
                                         qpos, jnp.int32(layer), jnp.int32(0),
                                         impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, err_msg=impl)


@pytest.mark.slow
def test_length_zero_rows_produce_zero_not_nan():
    """An all-masked row (padding decode slot before its first token) must
    return exactly 0, not NaN and not an average of garbage pages."""
    q, kp, vp, tables, _, _, _ = _random_case(KEY, 3, 4, 2, 32, 8, 16, 4)
    counts = jnp.asarray([[8, 4, 0, 0], [0, 0, 0, 0], [1, 0, 0, 0]],
                         jnp.int32)
    starts = jnp.concatenate([jnp.zeros((3, 1), jnp.int32),
                              jnp.cumsum(counts, axis=1)[:, :-1]], axis=1)
    qpos = counts.sum(axis=1) - 1
    for impl in ("interpret", "jnp"):
        out = np.asarray(ops.paged_decode_attention(
            q, kp, vp, tables, counts, starts, qpos,
            jnp.int32(0), jnp.int32(0), impl=impl))
        assert np.isfinite(out).all(), impl
        assert np.abs(out[1]).max() == 0.0, impl
        assert np.abs(out[0]).max() > 0.0, impl


@pytest.mark.parametrize("window", [3, 9])
@pytest.mark.slow
def test_sliding_window_and_softcap_parity(window):
    """Window masking works on absolute positions reconstructed from the run
    starts — a mid-slot tail shifts every later position, which is exactly
    what breaks if the kernel assumed page-aligned runs."""
    q, kp, vp, _, _, _, _ = _random_case(KEY, 2, 4, 2, 32, 8, 16, 4)
    tables = jnp.asarray([[3, 7, 1, 9], [5, 2, 0, 0]], jnp.int32)
    counts = jnp.asarray([[5, 3, 8, 2], [8, 5, 0, 0]], jnp.int32)
    starts = jnp.concatenate([jnp.zeros((2, 1), jnp.int32),
                              jnp.cumsum(counts, axis=1)[:, :-1]], axis=1)
    qpos = counts.sum(axis=1) - 1
    layer, cap = 2, 30.0
    dk, dv, lengths = _runs_to_dense(kp, vp, tables, counts, layer)
    want = L.decode_attention(q[:, None], dk, dv, pos=lengths,
                              window=window, logit_cap=cap)[:, 0]
    for impl in ("interpret", "jnp"):
        got = ops.paged_decode_attention(
            q, kp, vp, tables, counts, starts, qpos,
            jnp.int32(layer), jnp.int32(window), logit_cap=cap, impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, err_msg=impl)


@pytest.mark.slow
def test_single_layer_wrapper_matches_legacy_reference():
    """ops.paged_attention (the contiguous single-layer view) still honors
    the legacy lengths semantics through the layer-major kernel."""
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    B, H, KV, hd, page, n_pages, n_slots = 2, 8, 2, 64, 16, 8, 3
    q = jax.random.normal(k1, (B, H, hd))
    kp = jax.random.normal(k2, (n_pages, KV, page, hd))
    vp = jax.random.normal(k3, (n_pages, KV, page, hd))
    bt = jax.random.randint(k4, (B, n_slots), 0, n_pages)
    lengths = jnp.asarray([1, 37], jnp.int32)
    out = ops.paged_attention(q, kp, vp, bt, lengths, interpret=True)
    want = ref.reference_paged_attention(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


_PERM_SHAPE = dict(B=1, H=4, KV=2, hd=16, page=8, n_pages=12, n_slots=3)


def _check_permutation_invariance(perm, length):
    """kernel == ref.reference_paged_attention == dense decode_step
    attention for one physical page placement of the logical sequence."""
    s = _PERM_SHAPE
    k1, k2 = jax.random.split(KEY)
    q = jax.random.normal(k1, (s["B"], s["H"], s["hd"]))
    kv = jax.random.normal(k2, (s["n_slots"] * s["page"], s["KV"], s["hd"]))
    order = list(perm)[:s["n_slots"]]
    kp = jnp.zeros((s["n_pages"], s["KV"], s["page"], s["hd"]))
    vp = jnp.zeros_like(kp)
    for i, pid in enumerate(order):
        tile = kv[i * s["page"]:(i + 1) * s["page"]].swapaxes(0, 1)
        kp = kp.at[pid].set(tile)
        vp = vp.at[pid].set(tile * 0.5)
    bt = jnp.asarray([order], jnp.int32)
    lengths = jnp.asarray([length], jnp.int32)
    kern = ops.paged_attention(q, kp, vp, bt, lengths, interpret=True)
    oracle = ref.reference_paged_attention(q, kp, vp, bt, lengths)
    dense = L.decode_attention(
        q[:, None], kv[None, :length], kv[None, :length] * 0.5,
        pos=lengths)[:, 0]
    np.testing.assert_allclose(np.asarray(kern), np.asarray(oracle),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               atol=1e-4)


@pytest.mark.slow
def test_block_table_permutation_spot_checks():
    """Fixed placements incl. a mid-slot last token (length % page != 0) —
    runs even where hypothesis is unavailable."""
    _check_permutation_invariance(range(12), 20)
    _check_permutation_invariance([7, 3, 11, 0], 24)
    _check_permutation_invariance([5, 0, 9], 1)


@pytest.mark.slow
def test_hypothesis_block_table_permutation_property():
    """For ANY physical page placement of the same logical sequence:
    kernel == ref.reference_paged_attention == dense decode_step attention
    (the paged layout is a pure storage change)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    s = _PERM_SHAPE

    @settings(max_examples=20, deadline=None)
    @given(perm=st.permutations(range(s["n_pages"])),
           length=st.integers(1, s["n_slots"] * s["page"]))
    def check(perm, length):
        _check_permutation_invariance(perm, length)

    check()


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


def test_paged_decode_step_matches_decode_step(serving_setup):
    """paged_decode_step == decode_step logits on an unaligned multi-run
    layout driven through the real model (rope, GQA, scan over layers)."""
    cfg, params, _, _, _ = serving_setup
    bs, n_blocks = 8, 24
    B = 2
    lens = [21, 13]                      # runs: [8,8,6] and [8,6] (mid-slot)
    rng = np.random.default_rng(0)
    smax = max(lens) + 1
    k = jax.random.normal(KEY, (cfg.n_layers, B, smax, cfg.n_kv_heads,
                                cfg.hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 1), k.shape)
    mask = (np.arange(smax)[None] < np.asarray(lens)[:, None])[None, :, :,
                                                               None, None]
    cache = {"k": k * mask, "v": v * mask}
    # scatter the dense caches into paged planes with unaligned runs
    kp = jnp.zeros((cfg.n_layers, n_blocks, cfg.n_kv_heads, bs, cfg.hd))
    vp = jnp.zeros_like(kp)
    free = list(rng.permutation(n_blocks - 1) + 1)   # block 0 = scratch
    T = 6
    tables = np.zeros((B, T), np.int32)
    counts = np.zeros((B, T), np.int32)
    starts = np.zeros((B, T), np.int32)
    wblk = np.zeros((B,), np.int32)
    wslot = np.zeros((B,), np.int32)
    # run lengths cover lens[b] + 1 tokens: the final run's last slot is the
    # reserved position the new token is appended into (counts include it,
    # per the paged_decode_step contract)
    run_lens = {0: [8, 8, 6], 1: [8, 6]}
    for b in range(B):
        t = 0
        for j, c in enumerate(run_lens[b]):
            blk = free.pop()
            take = min(c, lens[b] - t)             # last run: slot reserved
            kp = kp.at[:, blk, :, :take].set(
                cache["k"][:, b, t:t + take].swapaxes(1, 2))
            vp = vp.at[:, blk, :, :take].set(
                cache["v"][:, b, t:t + take].swapaxes(1, 2))
            tables[b, j] = blk
            starts[b, j] = t
            t += take
        counts[b, :len(run_lens[b])] = run_lens[b]
        last = lens[b]                             # the new token's position
        assert sum(run_lens[b]) == last + 1
        wblk[b] = tables[b, len(run_lens[b]) - 1]
        wslot[b] = last - starts[b, len(run_lens[b]) - 1]
    toks = jnp.asarray([[3], [7]], jnp.int32)
    pos = jnp.asarray([lens[b] + 1 for b in range(B)], jnp.int32)
    want_logits, want_cache = M.decode_step(cfg, params, toks, cache, pos)
    got_logits, kp2, vp2 = M.paged_decode_step(
        cfg, params, toks, kp, vp, jnp.asarray(tables), jnp.asarray(counts),
        jnp.asarray(starts), jnp.asarray(wblk), jnp.asarray(wslot), pos,
        attn_impl="jnp")
    # the reduced model runs bf16 activations: online softmax vs padded
    # dense softmax reassociate differently, so logits agree to bf16 ULP
    # (bit-identical GREEDY TOKENS are asserted e2e below and in
    # test_serve_main.py; exact f32 parity is asserted kernel-level above)
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(want_logits),
                               atol=3e-2)
    for b in range(B):
        assert int(jnp.argmax(got_logits[b, -1])) == int(
            jnp.argmax(want_logits[b, -1]))
    # the appended KV landed at the advertised (block, slot) — compared at
    # bf16 tolerance since layer>0 projections see ULP-shifted activations
    bidx = jnp.arange(B)
    new_k = want_cache["k"][:, bidx, pos - 1]
    np.testing.assert_allclose(
        np.asarray(gather_slots(kp2, wblk, wslot), np.float32),
        np.asarray(new_k, np.float32), atol=2e-2)
    assert np.abs(np.asarray(gather_slots(vp2, wblk, wslot),
                             np.float32)).max() > 0


@pytest.mark.parametrize("padding_rows", [0, 2])
def test_decode_page_write_matches_token_scatter(padding_rows):
    """The decode step's KV write — one token a row, one page write a row,
    as ``paged_decode_step`` calls it — leaves every page but the scratch
    block bit-identical to the token scatter it replaced, with padding rows
    all aimed at slot 0 of the scratch block."""
    from repro.kvcache.paged import write_pages
    L_, n_blocks, KV, bs, hd, li = 2, 12, 2, 8, 4, 1
    rng = np.random.default_rng(padding_rows)
    live = 4
    wblk = np.zeros(live + padding_rows, np.int32)     # block 0 = scratch
    wslot = np.zeros(live + padding_rows, np.int32)
    wblk[:live] = rng.permutation(n_blocks - 1)[:live] + 1
    wslot[:live] = [0, 3, bs - 1, 5]
    B = len(wblk)
    pages = jnp.asarray(rng.standard_normal((L_, n_blocks, KV, bs, hd)),
                        jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, 1, KV, hd)))
    got = jax.jit(write_pages, static_argnums=6)(
        pages, jnp.int32(li), jnp.asarray(wblk)[:, None],
        jnp.asarray(wslot)[:, None], jnp.ones((B,), jnp.int32), k, 1)
    want = pages.at[li, wblk, :, wslot].set(k[:, 0].astype(pages.dtype))
    keep = np.arange(1, n_blocks)
    assert np.array_equal(np.asarray(got)[:, keep], np.asarray(want)[:, keep])


def test_runtime_paged_tokens_match_dense_and_tables_pack_runs(serving_setup):
    """e2e: --attn paged reproduces the dense engine's greedy tokens, and
    the packed run tables obey the slot-mapping contract (runs start at
    slot 0; unaligned shared tails appear as counts < block_size)."""
    from repro.serving.runtime import ContinuousRuntime
    cfg, params, corpus, idx, wl = serving_setup
    seen = {"midslot_tail": 0, "rows": 0}
    from repro.serving.config import EngineConfig
    rt = ContinuousRuntime(cfg, params, corpus, idx,
                           config=EngineConfig(top_k=2, attn="paged"))
    orig = rt._paged_decode_args

    def spy(batch):
        args = orig(batch)
        counts = np.asarray(args[2])
        for i, st in enumerate(batch):
            seen["rows"] += 1
            row = counts[i][counts[i] > 0]
            # non-final runs shorter than a block = shared unaligned tails
            if len(row) > 1 and (row[:-1] < rt.store.block_size).any():
                seen["midslot_tail"] += 1
            assert row.sum() == st.length + 1
        return args

    rt._paged_decode_args = spy
    res_p = rt.serve(wl, max_new_tokens=4)
    rt_d = ContinuousRuntime(cfg, params, corpus, idx,
                             config=EngineConfig(top_k=2, attn="dense"))
    res_d = rt_d.serve(wl, max_new_tokens=4)
    assert [r.tokens for r in res_p] == [r.tokens for r in res_d]
    assert seen["rows"] > 0 and seen["midslot_tail"] > 0
    rt.tree.check_invariants()
    rt.store.pool.check()


def test_paged_step_never_materializes_dense_context(serving_setup):
    """Inspect the jaxpr of the paged decode step: no intermediate may reach
    the dense-gather footprint L*B*S*KV*hd the dense engine pays — the
    whole point of wiring the kernel is deleting that array from the
    steady-state loop.  (The pool planes themselves are threaded through
    unchanged and are allowed.)"""
    cfg, params, corpus, idx, wl = serving_setup
    from repro.serving.runtime import ContinuousRuntime
    from repro.serving.config import EngineConfig
    rt = ContinuousRuntime(cfg, params, corpus, idx, n_blocks=64,
                           config=EngineConfig(top_k=2, attn="paged"))
    rt.max_new_tokens = 4
    max_ctx = 2 * int(max(corpus.doc_lengths)) + 16
    n_slots = rt.store.pool.blocks_for_tokens(max_ctx) + 1
    S = n_slots * rt.store.block_size
    dense_elems = (cfg.n_layers * rt.sched.config.max_batch * S
                   * cfg.n_kv_heads * cfg.hd)
    pool_elems = int(np.prod(rt.store.k.shape))
    B, T = rt.sched.config.max_batch, n_slots + rt.top_k + 1
    jaxpr = jax.make_jaxpr(
        lambda p, toks, tb, ct, st_, pos, wb, ws, kp, vp:
        M.paged_decode_step(cfg, p, toks, kp, vp, tb, ct, st_, wb, ws, pos,
                            attn_impl="jnp"))(
        params, jnp.zeros((B, 1), jnp.int32),
        jnp.zeros((B, T), jnp.int32), jnp.zeros((B, T), jnp.int32),
        jnp.zeros((B, T), jnp.int32), jnp.ones((B,), jnp.int32),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        rt.store.k, rt.store.v)

    def max_interm(jpr):
        worst = 0
        for eqn in jpr.eqns:
            for val in eqn.params.values():
                for v in (val if isinstance(val, (list, tuple)) else [val]):
                    # duck-typed sub-jaxpr descent (jax.core.{Closed,}Jaxpr
                    # move between jax versions): ClosedJaxpr has .jaxpr,
                    # a raw Jaxpr has .eqns
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
