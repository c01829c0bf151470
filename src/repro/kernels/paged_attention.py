"""Paged decode attention straight from the serving pool (docs/ARCHITECTURE.md §3).

vLLM's PagedAttention reads KV from non-contiguous pages via per-SM gathers;
the TPU-native adaptation prefetches the request's block table into SMEM
(``PrefetchScalarGridSpec``) so the page index feeds the BlockSpec index_map,
and the DMA engine streams one (page x hd) KV tile HBM->VMEM per grid step
while the VPU/MXU consumes the previous one.

The kernel operates on the ``PagedKVStore``'s own layer-major, head-major
layout — ``k_pages/v_pages: (L, n_pages, KV, page, hd)`` — selecting the
layer through a prefetched scalar, so the serving runtime's decode step
attends IN PLACE: no per-iteration dense re-materialization of the cached
context.  Heads sit outside the page so that the tile the DMA streams has
``(page, hd)`` as its last two dims: the TPU compiler tiles the minor two
dims of a block by (8, 128) unless they span the whole array dim, which a
single KV head picked out of a ``(page, KV, hd)`` page would not.

Token-level slot-mapping contract (what PR 4's unaligned sharing produces,
see ``serving/runtime.py::_paginate``): a request's sequence is a list of
*runs*, one per table entry ``j`` — page ``tables[b, j]`` holds the
``counts[b, j]`` consecutive tokens starting at absolute position
``starts[b, j]``, always beginning at slot 0.  A shared knowledge-tree
segment whose document ends mid-block therefore contributes a tail run with
``counts < page``; the dead tail slots are masked, and the next document's
run starts in a fresh page.  ``counts[b, j] == 0`` marks an unused table
entry (its DMA still streams page ``tables[b, j]`` — point padding entries
at a valid scratch page).

grid = (batch, head, n_table_slots); online-softmax accumulator in VMEM
scratch, finalized at the last table slot.  GQA rides the index_map
(``h // (H // KV)``) so the repeated KV stream never materializes.  A row
whose runs are ALL empty (a padding decode slot) produces a zero output
vector rather than NaN.

``paged_decode_jnp`` is the same computation as a pure-jnp per-page gather +
online softmax ``lax.scan`` — the production CPU path (interpret-mode Pallas
is a correctness tool, not an execution engine), with identical masking
semantics.  ``kernels/ops.py`` dispatches between them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(meta_ref, tables_ref, counts_ref, starts_ref, qpos_ref,
                   q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   page: int, n_slots: int, scale: float, logit_cap: float):
    b = pl.program_id(0)
    ib = pl.program_id(2)

    @pl.when(ib == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (1, hd) — one token
    k = k_ref[0, 0, 0].astype(jnp.float32)       # (page, hd)
    v = v_ref[0, 0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    live = slot < counts_ref[b, ib]
    win = meta_ref[1]
    pos = starts_ref[b, ib] + slot
    # boolean or, not a select: Mosaic cannot lower a select on i1 vectors
    live &= (win <= 0) | (pos > qpos_ref[b] - win)
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # explicit zeroing of masked probabilities: when every slot so far is
    # masked, m_new == NEG_INF and exp(s - m_new) == 1 — without the where a
    # length-0 row would average the garbage pages instead of returning 0
    p = jnp.where(live, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ib == n_slots - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,              # (B, H, hd) — one decode token per sequence
    k_pages: jax.Array,        # (L, n_pages, KV, page, hd) — the pool arrays
    v_pages: jax.Array,
    tables: jax.Array,         # (B, n_slots) int32 page ids (runs, in order)
    counts: jax.Array,         # (B, n_slots) live tokens per run (0 = unused)
    starts: jax.Array,         # (B, n_slots) absolute position of run start
    qpos: jax.Array,           # (B,) absolute position of the query token
    layer,                     # int32 scalar — which layer plane to read
    window,                    # int32 scalar — sliding window (0 = global)
    *,
    logit_cap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    _, _, KV, page, _ = k_pages.shape
    R = H // KV
    n_slots = tables.shape[1]
    scale = hd ** -0.5

    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(window, jnp.int32)])
    kernel = functools.partial(_decode_kernel, page=page, n_slots=n_slots,
                               scale=scale, logit_cap=logit_cap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,    # meta, tables, counts, starts, qpos
        grid=(B, H, n_slots),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd),
                         lambda b, h, ib, meta, tbl, cnt, st, qp:
                         (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, page, hd),
                         lambda b, h, ib, meta, tbl, cnt, st, qp:
                         (meta[0], tbl[b, ib], h // R, 0, 0)),
            pl.BlockSpec((1, 1, 1, page, hd),
                         lambda b, h, ib, meta, tbl, cnt, st, qp:
                         (meta[0], tbl[b, ib], h // R, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd),
                               lambda b, h, ib, meta, tbl, cnt, st, qp:
                               (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="paged_decode",
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        interpret=interpret,
    )(meta, tables, counts, starts, qpos, q[:, :, None], k_pages, v_pages)
    return out[:, :, 0]


def paged_decode_jnp(
    q: jax.Array,              # (B, H, hd)
    k_pages: jax.Array,        # (L, n_pages, KV, page, hd)
    v_pages: jax.Array,
    tables: jax.Array,         # (B, n_slots)
    counts: jax.Array,
    starts: jax.Array,
    qpos: jax.Array,           # (B,)
    layer,
    window,
    *,
    logit_cap: float = 0.0,
) -> jax.Array:
    """Per-page gather + online softmax, pure jnp (the CPU execution path).

    Peak live memory per step is one (B, KV, page, hd) tile — never the
    dense (B, S, KV, hd) context, let alone all L layers of it.
    """
    B, H, hd = q.shape
    KV, page = k_pages.shape[2], k_pages.shape[3]
    R = H // KV
    scale = hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, KV, R, hd)
    n_slots = tables.shape[1]
    win = jnp.asarray(window, jnp.int32)
    slot = jnp.arange(page, dtype=jnp.int32)

    def body(carry, j):
        m, l, acc = carry
        pid = tables[:, j]                                 # (B,)
        k = k_pages[layer, pid].astype(jnp.float32)        # (B, KV, page, hd)
        v = v_pages[layer, pid].astype(jnp.float32)
        s = jnp.einsum("bgrd,bgpd->bgrp", qf, k)
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        live = slot[None, :] < counts[:, j, None]          # (B, page)
        pos = starts[:, j, None] + slot[None, :]
        live &= jnp.where(win > 0, pos > qpos[:, None] - win, True)
        lb = live[:, None, None, :]
        s = jnp.where(lb, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(lb, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bgrp,bgpd->bgrd", p, v)
        return (m_new, l, acc), None

    init = (jnp.full((B, KV, R), NEG_INF, jnp.float32),
            jnp.zeros((B, KV, R), jnp.float32),
            jnp.zeros((B, KV, R, hd), jnp.float32))
    (m, l, acc), _ = lax.scan(body, init, jnp.arange(n_slots))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, hd).astype(q.dtype)


def paged_attention(
    q: jax.Array,              # (B, H, hd)
    k_pages: jax.Array,        # (n_pages, KV, page, hd) — single-layer view
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, n_slots) int32 page ids
    lengths: jax.Array,        # (B,) valid token counts
    *,
    interpret: bool = False,
) -> jax.Array:
    """Single-layer convenience wrapper over the layer-major kernel:
    contiguous semantics (page ``j`` holds positions ``[j*page, ...)`` up to
    ``lengths[b]``), kept for the kernel parity sweep and benches."""
    page = k_pages.shape[2]
    n_slots = block_tables.shape[1]
    off = jnp.arange(n_slots, dtype=jnp.int32)[None] * page      # (1, n_slots)
    counts = jnp.clip(lengths[:, None] - off, 0, page).astype(jnp.int32)
    starts = jnp.broadcast_to(off, block_tables.shape).astype(jnp.int32)
    return paged_decode_attention(
        q, k_pages[None], v_pages[None], block_tables, counts, starts,
        jnp.maximum(lengths - 1, 0).astype(jnp.int32),
        jnp.int32(0), jnp.int32(0), interpret=interpret)
