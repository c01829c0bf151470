"""Pure-jnp oracles for the Pallas kernels (tests assert_allclose vs these)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def reference_prefix_attention(q, k, v, *, prefix_len: int, window: int = 0,
                               logit_cap: float = 0.0):
    """q: (B, H, Sq, hd); k/v: (B, KV, Skv, hd) with Skv = prefix_len + Sq."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    R = H // KV
    kf = jnp.repeat(k, R, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, R, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) * hd ** -0.5
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)
    q_pos = prefix_len + jnp.arange(Sq)
    k_pos = jnp.arange(Skv)
    mask = k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def reference_paged_prefill(q, k_pages, v_pages, tables, counts, starts,
                            q_start, q_len, layer, window=0, logit_cap=0.0):
    """Dense oracle for the layer-major paged prefill kernel.

    q: (B, H, Sq, hd); k/v_pages: (L, n_pages, KV, page, hd); tables/counts/
    starts: (B, n_slots) run descriptors (see paged_attention.py docstring);
    q_start: (B,) absolute position of query row 0; q_len: (B,) valid query
    rows — invalid (ragged-padding) rows return exact zeros.
    """
    B, H, Sq, hd = q.shape
    KV, page = k_pages.shape[2], k_pages.shape[3]
    R = H // KV
    nb = tables.shape[1]
    k = k_pages[layer][tables].swapaxes(2, 3)    # (B, nb, page, KV, hd)
    v = v_pages[layer][tables].swapaxes(2, 3)
    k = k.reshape(B, nb * page, KV, hd)
    v = v.reshape(B, nb * page, KV, hd)
    kf = jnp.repeat(k, R, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v, R, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bkhd->bhqk", q.astype(jnp.float32), kf) * hd ** -0.5
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)
    slot = jnp.arange(page)
    live = slot[None, None] < counts[..., None]              # (B, nb, page)
    kpos = starts[..., None] + slot[None, None]
    live = live.reshape(B, nb * page)
    kpos = kpos.reshape(B, nb * page)
    qpos = q_start[:, None] + jnp.arange(Sq)[None]           # (B, Sq)
    mask = live[:, None] & (kpos[:, None] <= qpos[..., None])
    mask &= (jnp.arange(Sq)[None] < q_len[:, None])[..., None]
    if window:
        mask &= kpos[:, None] > qpos[..., None] - window
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, None], p, 0.0)     # all-masked row -> 0, not NaN
    return jnp.einsum("bhqk,bkhd->bhqd", p, vf).astype(q.dtype)


def reference_paged_decode(q, k_pages, v_pages, tables, counts, starts, qpos,
                           layer, window=0, logit_cap=0.0):
    """Dense oracle for the layer-major paged decode kernel.

    q: (B, H, hd); k/v_pages: (L, n_pages, KV, page, hd); tables/counts/
    starts: (B, n_slots) run descriptors (see paged_attention.py docstring);
    qpos: (B,) absolute query position; layer selects the page plane.
    """
    B, H, hd = q.shape
    KV, page = k_pages.shape[2], k_pages.shape[3]
    R = H // KV
    nb = tables.shape[1]
    k = k_pages[layer][tables].swapaxes(2, 3)    # (B, nb, page, KV, hd)
    v = v_pages[layer][tables].swapaxes(2, 3)
    k = k.reshape(B, nb * page, KV, hd)
    v = v.reshape(B, nb * page, KV, hd)
    kf = jnp.repeat(k, R, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v, R, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), kf) * hd ** -0.5
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)
    slot = jnp.arange(page)
    live = slot[None, None] < counts[..., None]              # (B, nb, page)
    pos = starts[..., None] + slot[None, None]
    if window:
        live &= pos > qpos[:, None, None] - window
    live = live.reshape(B, nb * page)
    s = jnp.where(live[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(live[:, None], p, 0.0)     # all-masked row -> 0, not NaN/avg
    return jnp.einsum("bhk,bkhd->bhd", p, vf).astype(q.dtype)


def reference_paged_attention(q, k_pages, v_pages, block_tables, lengths):
    """q: (B, H, hd); k/v_pages: (n_pages, KV, page, hd);
    block_tables: (B, n_blocks_max) int32; lengths: (B,) valid tokens."""
    B, H, hd = q.shape
    n_pages, KV, page, _ = k_pages.shape
    R = H // KV
    nb = block_tables.shape[1]
    # gather per-request contiguous KV
    k = k_pages[block_tables].swapaxes(2, 3)     # (B, nb, page, KV, hd)
    v = v_pages[block_tables].swapaxes(2, 3)
    k = k.reshape(B, nb * page, KV, hd)
    v = v.reshape(B, nb * page, KV, hd)
    kf = jnp.repeat(k, R, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v, R, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), kf) * hd ** -0.5
    mask = jnp.arange(nb * page)[None] < lengths[:, None]
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, vf).astype(q.dtype)
