"""Paged ragged prefill attention straight from the serving pool
(docs/ARCHITECTURE.md §3) — the prefill twin of ``paged_attention.py``.

A prefill chunk's queries attend over (a) the cached prefix pages already
resident in the ``PagedKVStore``'s layer-major, head-major
``(L, n_pages, KV, page, hd)`` planes (``(page, hd)`` minor, the tile shape
the TPU compiler accepts — see ``paged_attention.py``) and (b) the chunk's own new KV, which the model step scatters into its
freshly allocated pages *before* calling attention.  Both live behind the
same run-table slot-mapping contract as paged decode (``tables/counts/
starts``: page ``tables[b, j]`` holds ``counts[b, j]`` consecutive tokens
starting at absolute position ``starts[b, j]``, always from slot 0;
``counts == 0`` marks an unused entry pointed at a scratch page), so cached
document tails ending mid-block need no re-copy — their dead slots mask.

What prefill adds over decode is a *block of query rows per request* instead
of one token: query row ``i`` of request ``b`` sits at absolute position
``q_start[b] + i`` and is valid iff ``i < q_len[b]``.  Invalid rows (ragged
batch padding) are fully masked and produce exact zeros — not NaN, not an
average of garbage pages — which makes a padded batched call row-independent:
each request's outputs are identical whatever else shares the batch, the
property the any-chunk-size token-identity guarantee rests on.

grid = (batch, head, q_block, n_table_slots) with the KV slot innermost:
online-softmax accumulator tiles (block_q, hd) in VMEM scratch, initialized
at slot 0 and finalized at the last slot, exactly the decode kernel's scheme
lifted from one query row to ``block_q``.  GQA rides the index_map
(``h // (H // KV)``); sliding windows and the logit softcap match decode
(cap applied pre-mask, window on absolute positions).

``paged_prefill_jnp`` is the same computation as a per-page gather + online
softmax ``lax.scan`` — the production CPU path, identical masking semantics.
``kernels/ops.py`` dispatches between them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _prefill_kernel(meta_ref, tables_ref, counts_ref, starts_ref, qstart_ref,
                    qlen_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                    l_ref, *, page: int, n_slots: int, block_q: int,
                    scale: float, logit_cap: float):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ib = pl.program_id(3)

    @pl.when(ib == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (block_q, hd)
    k = k_ref[0, 0, 0].astype(jnp.float32)       # (page, hd)
    v = v_ref[0, 0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)
    qrow = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, page), 0)
    qpos = qstart_ref[b] + qrow
    slot = jax.lax.broadcasted_iota(jnp.int32, (block_q, page), 1)
    kpos = starts_ref[b, ib] + slot
    live = slot < counts_ref[b, ib]
    live &= kpos <= qpos                         # causal, absolute positions
    live &= qrow < qlen_ref[b]                   # ragged-padding query rows
    win = meta_ref[1]
    # boolean or, not a select: Mosaic cannot lower a select on i1 vectors
    live &= (win <= 0) | (kpos > qpos - win)
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # explicit zeroing of masked probabilities: a fully-masked row (padding,
    # or a valid row whose visible set is still empty) has m_new == NEG_INF
    # and exp(s - m_new) == 1 — without the where it would average garbage
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


def paged_prefill_attention(
    q: jax.Array,              # (B, H, Sq, hd) — one prefill chunk per row
    k_pages: jax.Array,        # (L, n_pages, KV, page, hd) — the pool arrays
    v_pages: jax.Array,
    tables: jax.Array,         # (B, n_slots) int32 page ids (runs, in order)
    counts: jax.Array,         # (B, n_slots) live tokens per run (0 = unused)
    starts: jax.Array,         # (B, n_slots) absolute position of run start
    q_start: jax.Array,        # (B,) absolute position of query row 0
    q_len: jax.Array,          # (B,) valid query rows (rest are padding)
    layer,                     # int32 scalar — which layer plane to read
    window,                    # int32 scalar — sliding window (0 = global)
    *,
    logit_cap: float = 0.0,
    block_q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, hd = q.shape
    _, _, KV, page, _ = k_pages.shape
    R = H // KV
    n_slots = tables.shape[1]
    scale = hd ** -0.5

    block_q = min(block_q, max(Sq, 8))
    pad_q = (-Sq) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    nq = (Sq + pad_q) // block_q

    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(window, jnp.int32)])
    kernel = functools.partial(_prefill_kernel, page=page, n_slots=n_slots,
                               block_q=block_q, scale=scale,
                               logit_cap=logit_cap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,    # meta, tables, counts, starts, q_start, q_len
        grid=(B, H, nq, n_slots),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, iq, ib, meta, tbl, cnt, st, qs, ql:
                         (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, page, hd),
                         lambda b, h, iq, ib, meta, tbl, cnt, st, qs, ql:
                         (meta[0], tbl[b, ib], h // R, 0, 0)),
            pl.BlockSpec((1, 1, 1, page, hd),
                         lambda b, h, iq, ib, meta, tbl, cnt, st, qs, ql:
                         (meta[0], tbl[b, ib], h // R, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, iq, ib, meta, tbl, cnt, st, qs, ql:
                               (b, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="paged_prefill",
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, hd), q.dtype),
        interpret=interpret,
    )(meta, tables, counts, starts, q_start, q_len, q, k_pages, v_pages)
    return out[:, :, :Sq]


def paged_prefill_jnp(
    q: jax.Array,              # (B, H, Sq, hd)
    k_pages: jax.Array,        # (L, n_pages, KV, page, hd)
    v_pages: jax.Array,
    tables: jax.Array,         # (B, n_slots)
    counts: jax.Array,
    starts: jax.Array,
    q_start: jax.Array,        # (B,)
    q_len: jax.Array,          # (B,)
    layer,
    window,
    *,
    logit_cap: float = 0.0,
) -> jax.Array:
    """Per-page gather + online softmax, pure jnp (the CPU execution path).

    Peak live memory per step is one (B, KV, page, hd) KV tile plus the
    (B, H, Sq, page) score tile — never the dense (B, S, KV, hd) context,
    let alone all L layers of it.
    """
    B, H, Sq, hd = q.shape
    KV, page = k_pages.shape[2], k_pages.shape[3]
    R = H // KV
    scale = hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, KV, R, Sq, hd)
    n_slots = tables.shape[1]
    win = jnp.asarray(window, jnp.int32)
    slot = jnp.arange(page, dtype=jnp.int32)
    qrow = jnp.arange(Sq, dtype=jnp.int32)
    qpos = q_start[:, None] + qrow[None]                   # (B, Sq)
    qvalid = qrow[None] < q_len[:, None]                   # (B, Sq)

    def body(carry, j):
        m, l, acc = carry
        pid = tables[:, j]                                 # (B,)
        k = k_pages[layer, pid].astype(jnp.float32)        # (B, KV, page, hd)
        v = v_pages[layer, pid].astype(jnp.float32)
        s = jnp.einsum("bgrqd,bgpd->bgrqp", qf, k)
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        kpos = starts[:, j, None] + slot[None]             # (B, page)
        live = slot[None] < counts[:, j, None]             # (B, page)
        mask = live[:, None] & (kpos[:, None] <= qpos[..., None])
        mask &= qvalid[..., None]                          # (B, Sq, page)
        mask &= jnp.where(win > 0, kpos[:, None] > qpos[..., None] - win,
                          True)
        mb = mask[:, None, None]                           # (B,1,1,Sq,page)
        s = jnp.where(mb, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(mb, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bgrqp,bgpd->bgrqd", p, v)
        return (m_new, l, acc), None

    init = (jnp.full((B, KV, R, Sq), NEG_INF, jnp.float32),
            jnp.zeros((B, KV, R, Sq), jnp.float32),
            jnp.zeros((B, KV, R, Sq, hd), jnp.float32))
    (m, l, acc), _ = lax.scan(body, init, jnp.arange(n_slots))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, Sq, hd).astype(q.dtype)
