"""Kernel-layer bench: Pallas prefix-attention grid/VMEM accounting + CPU
oracle agreement, the jnp flash path wall-clock (the actual CPU compute
path; interpret-mode kernel timing is not meaningful), and the serving-shape
decode comparison: dense-gather ``decode_step`` vs kernel-backed
``paged_decode_step`` straight from the pool (the `--attn dense|paged` A/B
that PR 5 wired into the runtime).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import smoke_clamp
from repro.configs import get_reduced
from repro.kernels import ops, ref
from repro.kvcache.paged import gather_slots
from repro.models import layers as L
from repro.models import model as M


def _paged_decode_rows() -> list:
    """Dense-gather vs paged decode at serving shapes: one decode iteration
    of the reduced model, B requests of ctx tokens in a 16-token-block pool
    (the continuous runtime's exact layout), steady-state (post-jit)."""
    rows = []
    cfg = get_reduced("qwen2-0.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    bs = 16
    B = 4
    ctx = smoke_clamp(512, 64)
    reps = smoke_clamp(10, 2)
    nb_req = -(-ctx // bs)
    n_blocks = B * nb_req + 1                       # block 0 = scratch
    S = nb_req * bs
    key = jax.random.PRNGKey(1)
    kp = jax.random.normal(key, (cfg.n_layers, n_blocks, cfg.n_kv_heads, bs,
                                 cfg.hd))
    vp = kp * 0.5
    toks = jnp.ones((B, 1), jnp.int32)
    pos = jnp.full((B,), ctx, jnp.int32)            # ctx incl. the new token
    # request b owns blocks [1 + b*nb_req, ...) — contiguous runs
    tables = np.asarray([[1 + b * nb_req + j for j in range(nb_req)]
                         for b in range(B)], np.int32)
    blk_map = np.repeat(tables, bs, axis=1)         # (B, S) token-level maps
    slot_map = np.tile(np.arange(S, dtype=np.int32) % bs, (B, 1))
    counts = np.full((B, nb_req), bs, np.int32)
    counts[:, -1] = ctx - (nb_req - 1) * bs
    starts = np.asarray([[j * bs for j in range(nb_req)]] * B, np.int32)
    wblk = tables[:, (ctx - 1) // bs]
    wslot = np.full((B,), (ctx - 1) % bs, np.int32)

    def dense_step(params, toks, blk_map, slot_map, lengths, kp, vp):
        k = gather_slots(kp, blk_map, slot_map)     # (L, B, S, KV, hd)
        v = gather_slots(vp, blk_map, slot_map)
        logits, _ = M.decode_step(cfg, params, toks, {"k": k, "v": v},
                                  lengths + 1)
        return jnp.argmax(logits[:, -1], axis=-1)

    def paged_step(params, toks, tables, counts, starts, pos, wblk, wslot,
                   kp, vp):
        logits, kp, vp = M.paged_decode_step(
            cfg, params, toks, kp, vp, tables, counts, starts, wblk, wslot,
            pos)
        return jnp.argmax(logits[:, -1], axis=-1), kp, vp

    dense = jax.jit(dense_step)
    paged = jax.jit(paged_step, donate_argnums=(8, 9))
    lengths = pos - 1
    args_d = (jnp.asarray(toks), jnp.asarray(blk_map), jnp.asarray(slot_map),
              jnp.asarray(lengths))
    args_p = (jnp.asarray(toks), jnp.asarray(tables), jnp.asarray(counts),
              jnp.asarray(starts), jnp.asarray(pos), jnp.asarray(wblk),
              jnp.asarray(wslot))
    dense(params, *args_d, kp, vp).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out_d = dense(params, *args_d, kp, vp)
    out_d.block_until_ready()
    dt_d = (time.perf_counter() - t0) / reps
    _, kp, vp = paged(params, *args_p, kp, vp)      # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out_p, kp, vp = paged(params, *args_p, kp, vp)
    out_p.block_until_ready()
    dt_p = (time.perf_counter() - t0) / reps
    if not bool((np.asarray(out_d) == np.asarray(out_p)).all()):
        # hard-fail the smoke lane: paged vs dense greedy-token divergence
        # is the regression this bench exists to catch, not a number to log
        raise RuntimeError(
            f"paged decode diverged from dense decode at bench shapes: "
            f"dense={np.asarray(out_d).tolist()} "
            f"paged={np.asarray(out_p).tolist()}")
    gathered = cfg.n_layers * B * S * cfg.n_kv_heads * cfg.hd
    rows.append((f"kernel/decode_dense_gather/B{B}_ctx{ctx}", dt_d * 1e6,
                 f"dense_elems={gathered} per_iter"))
    rows.append((f"kernel/decode_paged/B{B}_ctx{ctx}", dt_p * 1e6,
                 f"speedup_vs_dense={dt_d / max(dt_p, 1e-12):.2f}x "
                 f"tokens_match=True"))
    return rows


def _paged_prefill_rows() -> list:
    """Dense re-materialization vs paged ragged prefill at serving shapes:
    one chunked-prefill iteration of B requests, each with a cached prefix
    resident in the pool.  The dense baseline is the retired steady-state
    path — gather the prefix pages into a dense (L, 1, pref, KV, hd) cache
    and run a concat prefill per request (the dense engine runs one request
    per iteration); the paged path is ONE batched ``paged_prefill_step``
    reading the prefix pages in place and scattering the chunk KV into its
    own pages."""
    rows = []
    cfg = get_reduced("qwen2-0.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    bs = 16
    B = 4
    pref = smoke_clamp(256, 48)     # cached prefix tokens per request
    n = smoke_clamp(64, 16)         # chunk tokens per request
    reps = smoke_clamp(10, 2)
    total = pref + n
    nb_req = -(-total // bs)
    n_blocks = B * nb_req + 1                       # block 0 = scratch
    key = jax.random.PRNGKey(3)
    kp = jax.random.normal(key, (cfg.n_layers, n_blocks, cfg.n_kv_heads, bs,
                                 cfg.hd), cfg.jdtype)
    vp = kp * 0.5
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(B, n)).astype(np.int32)
    tables = np.asarray([[1 + b * nb_req + j for j in range(nb_req)]
                         for b in range(B)], np.int32)
    counts = np.full((B, nb_req), bs, np.int32)
    counts[:, -1] = total - (nb_req - 1) * bs
    starts = np.asarray([[j * bs for j in range(nb_req)]] * B, np.int32)
    pos = np.arange(pref, total)
    wblk = tables[:, pos // bs]
    wslot = np.tile((pos % bs).astype(np.int32), (B, 1))
    blk_map = np.repeat(tables, bs, axis=1)[:, :pref]
    slot_map = np.tile(np.arange(nb_req * bs, dtype=np.int32) % bs,
                       (B, 1))[:, :pref]

    def dense_one(params, toks_b, blk_b, slot_b, kp, vp):
        pc = {"k": gather_slots(kp, blk_b, slot_b),
              "v": gather_slots(vp, blk_b, slot_b)}
        logits, _ = M.prefill(cfg, params, {"tokens": toks_b},
                              prefix_cache=pc, prefix_len=pref)
        return jnp.argmax(logits[:, -1], axis=-1)

    def paged_step(params, toks, tables, counts, starts, qs, ql, wblk, wslot,
                   kp, vp):
        logits, kp, vp = M.paged_prefill_step(
            cfg, params, toks, kp, vp, tables, counts, starts, qs, ql,
            wblk, wslot)
        return jnp.argmax(logits[:, 0], axis=-1), kp, vp

    dense = jax.jit(dense_one)
    paged = jax.jit(paged_step, donate_argnums=(9, 10))
    args_d = [(jnp.asarray(toks[b:b + 1]), jnp.asarray(blk_map[b:b + 1]),
               jnp.asarray(slot_map[b:b + 1])) for b in range(B)]
    args_p = (jnp.asarray(toks), jnp.asarray(tables), jnp.asarray(counts),
              jnp.asarray(starts), jnp.full((B,), pref, jnp.int32),
              jnp.full((B,), n, jnp.int32), jnp.asarray(wblk),
              jnp.asarray(wslot))
    out_d = jnp.concatenate([dense(params, *a, kp, vp) for a in args_d])
    out_d.block_until_ready()                       # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out_d = jnp.concatenate([dense(params, *a, kp, vp) for a in args_d])
    out_d.block_until_ready()
    dt_d = (time.perf_counter() - t0) / reps
    _, kp, vp = paged(params, *args_p, kp, vp)      # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out_p, kp, vp = paged(params, *args_p, kp, vp)
    out_p.block_until_ready()
    dt_p = (time.perf_counter() - t0) / reps
    if not bool((np.asarray(out_d) == np.asarray(out_p)).all()):
        # hard-fail the smoke lane, exactly like the decode A/B above
        raise RuntimeError(
            f"paged prefill diverged from dense prefill at bench shapes: "
            f"dense={np.asarray(out_d).tolist()} "
            f"paged={np.asarray(out_p).tolist()}")
    gathered = cfg.n_layers * B * pref * cfg.n_kv_heads * cfg.hd
    rows.append((f"kernel/prefill_dense_gather/B{B}_pref{pref}_n{n}",
                 dt_d * 1e6, f"dense_elems={gathered} per_iter"))
    rows.append((f"kernel/prefill_paged/B{B}_pref{pref}_n{n}", dt_p * 1e6,
                 f"speedup_vs_dense={dt_d / max(dt_p, 1e-12):.2f}x "
                 f"tokens_match=True"))
    return rows


def run() -> list:
    rows = []
    # VMEM footprint per grid cell for production tile sizes
    for (bq, bk, hd) in ((128, 128, 128), (256, 512, 128), (128, 128, 256)):
        vmem = (bq * hd + 2 * bk * hd) * 2 + (bq * hd + 2 * bq) * 4 \
            + bq * bk * 4
        rows.append((f"kernel/prefix_attn/tile_q{bq}_k{bk}_hd{hd}",
                     vmem / 1024,
                     f"vmem_kib={vmem / 1024:.0f} fits_16MiB="
                     f"{vmem < 16 * 2**20}"))
    # correctness spot check (interpret mode)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    B, H, KV, Sq, P, hd = 1, 4, 2, 32, 32, 64
    q = jax.random.normal(k1, (B, H, Sq, hd), jnp.float32)
    k = jax.random.normal(k2, (B, KV, P + Sq, hd), jnp.float32)
    v = jax.random.normal(k3, (B, KV, P + Sq, hd), jnp.float32)
    t0 = time.perf_counter()
    out = ops.prefix_attention(q, k, v, prefix_len=P, block_q=16, block_k=16,
                               interpret=True)
    dt = time.perf_counter() - t0
    err = float(jnp.abs(
        out - ref.reference_prefix_attention(q, k, v, prefix_len=P)).max())
    rows.append(("kernel/prefix_attn/interpret_allclose", dt * 1e6,
                 f"max_err={err:.1e} ok={err < 1e-4}"))
    # jnp flash wall clock (CPU execution path used by the tiny engine)
    qf = q.transpose(0, 2, 1, 3)
    kf = k.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    fn = jax.jit(lambda q, k, v: L.flash_attention(q, k, v, q_offset=P))
    fn(qf, kf, vf).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        fn(qf, kf, vf).block_until_ready()
    rows.append(("kernel/flash_jnp/cpu_wallclock",
                 (time.perf_counter() - t0) / 10 * 1e6, "jit path"))
    rows.extend(_paged_decode_rows())
    rows.extend(_paged_prefill_rows())
    return rows
