"""Unified decoder-only model covering all assigned architecture families.

Entry points (all pure functions of (cfg, params, ...)):

  init_params(cfg, key)                 -> params pytree
  forward(cfg, params, inputs)          -> logits            (training path)
  prefill(cfg, params, inputs, prefix)  -> logits, cache     (serving prefill,
                                           optionally on top of a cached
                                           document-prefix — the RAGCache hook)
  decode_step(cfg, params, tokens, cache, pos) -> logits, cache

Layers are stacked and scanned (`lax.scan`) so 48–80-layer configs lower to a
small HLO even under 512-way SPMD partitioning.  Per-layer heterogeneity
(sliding-window vs global attention) rides along as a scanned int array.
The xLSTM family scans over *periods* (k−1 mLSTM blocks + 1 sLSTM block) so
heterogeneous block types need no dead parameters.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.kvcache.paged import max_write_runs, write_pages
from repro.models.config import ModelConfig
from repro.models import layers as L


# ==========================================================================
# parameter init
# ==========================================================================

def _norm_init(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale)


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    D, F, V, nl = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.jdtype
    keys = iter(jax.random.split(key, 64))
    scale = 0.02
    out_scale = scale / (2 * nl) ** 0.5

    def mk(shape, s=scale):
        return _norm_init(next(keys), shape, s).astype(dt)

    params: Dict[str, Any] = {}
    if cfg.n_codebooks:
        params["embed"] = mk((cfg.n_codebooks, V, D))
    else:
        params["embed"] = mk((V, D))
    if cfg.family == "vlm":
        params["vision_proj"] = mk((D, D))
    params["final_norm"] = jnp.zeros((D,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = mk((D, V * max(1, cfg.n_codebooks)))

    if cfg.family == "ssm":
        params["blocks"] = _init_xlstm_blocks(cfg, next(keys))
        return params

    blk: Dict[str, Any] = {
        "ln1": jnp.zeros((nl, D), dt),
        "wq": mk((nl, D, H * hd)),
        "wk": mk((nl, D, KV * hd)),
        "wv": mk((nl, D, KV * hd)),
        "wo": mk((nl, H * hd, D), out_scale),
        "ln2": jnp.zeros((nl, D), dt),
    }
    if cfg.qkv_bias:
        blk["bq"] = jnp.zeros((nl, H * hd), dt)
        blk["bk"] = jnp.zeros((nl, KV * hd), dt)
        blk["bv"] = jnp.zeros((nl, KV * hd), dt)
    if cfg.moe_experts:
        E = cfg.moe_experts
        blk["router"] = mk((nl, D, E))
        blk["wg"] = mk((nl, E, D, F))
        blk["wu"] = mk((nl, E, D, F))
        blk["wd"] = mk((nl, E, F, D), out_scale)
    else:
        blk["wg"] = mk((nl, D, F))
        blk["wu"] = mk((nl, D, F))
        blk["wd"] = mk((nl, F, D), out_scale)
    if cfg.family == "hybrid":
        N = cfg.ssm_state
        blk["ssm_ln"] = jnp.zeros((nl, D), dt)
        blk["ssm_in"] = mk((nl, D, H * hd))
        blk["ssm_dt"] = mk((nl, D, H))
        blk["ssm_B"] = mk((nl, D, N))
        blk["ssm_C"] = mk((nl, D, N))
        blk["ssm_A"] = -jnp.exp(
            _norm_init(next(keys), (nl, H, hd, N), 1.0)
        ).astype(jnp.float32)
        blk["ssm_D"] = jnp.ones((nl, H, hd), jnp.float32)
        blk["ssm_out"] = mk((nl, H * hd, D), out_scale)
    params["blocks"] = blk
    return params


def _init_xlstm_blocks(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    D = cfg.d_model
    Dp = int(cfg.proj_factor * D)
    H = cfg.n_heads
    hd_s = D // H                      # sLSTM head dim (model space)
    F2 = max(128, (4 * D // 3) // 128 * 128)
    dt = cfg.jdtype
    keys = iter(jax.random.split(key, 32))
    scale = 0.02
    out_scale = scale / (2 * cfg.n_layers) ** 0.5

    def mk(lead, shape, s=scale):
        return _norm_init(next(keys), lead + shape, s).astype(dt)

    if cfg.slstm_every > 0:
        period = cfg.slstm_every
        n_periods = cfg.n_layers // period
        m_lead = (n_periods, period - 1)
        s_lead = (n_periods,)
    else:
        m_lead = (cfg.n_layers,)
        s_lead = (0,)

    mblk = {
        "ln": jnp.zeros(m_lead + (D,), dt),
        "w_up": mk(m_lead, (D, 2 * Dp)),
        "conv_w": mk(m_lead, (cfg.conv_kernel, Dp)),
        "wq": mk(m_lead, (Dp, Dp)),
        "wk": mk(m_lead, (Dp, Dp)),
        "wv": mk(m_lead, (Dp, Dp)),
        "w_if": mk(m_lead, (Dp, 2 * H)),
        "b_if": jnp.zeros(m_lead + (2 * H,), dt),
        "gn": jnp.zeros(m_lead + (Dp,), dt),
        "w_down": mk(m_lead, (Dp, D), out_scale),
    }
    out = {"mlstm": mblk}
    if cfg.slstm_every > 0:
        out["slstm"] = {
            "ln": jnp.zeros(s_lead + (D,), dt),
            "w_x": mk(s_lead, (D, 4 * D)),
            "b_x": jnp.zeros(s_lead + (4 * D,), dt),
            "r_w": mk(s_lead, (H, hd_s, 4 * hd_s)),
            "gn": jnp.zeros(s_lead + (D,), dt),
            "ln2": jnp.zeros(s_lead + (D,), dt),
            "wg": mk(s_lead, (D, F2)),
            "wu": mk(s_lead, (D, F2)),
            "wd": mk(s_lead, (F2, D), out_scale),
        }
    return out


# ==========================================================================
# embeddings / heads
# ==========================================================================

def embed_inputs(cfg: ModelConfig, params, inputs: Dict[str, jax.Array]):
    """Returns (x, positions_offset_is_zero). Handles text/vlm/audio."""
    emb = params["embed"]
    if cfg.n_codebooks:
        toks = inputs["tokens"]                       # (B, K, S)
        x = jnp.zeros(toks.shape[:1] + toks.shape[2:] + (cfg.d_model,), cfg.jdtype)
        for kk in range(cfg.n_codebooks):
            x = x + jnp.take(emb[kk], toks[:, kk], axis=0)
        return x
    toks = inputs["tokens"]                           # (B, S)
    x = jnp.take(emb, toks, axis=0)
    if cfg.family == "vlm" and "patch_embeds" in inputs:
        pe = inputs["patch_embeds"].astype(cfg.jdtype)          # (B, Simg, D)
        pe = L.dense(pe, params["vision_proj"])
        x = jnp.concatenate([pe, x], axis=1)
    return x


def lm_logits(cfg: ModelConfig, params, x: jax.Array) -> jax.Array:
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        emb = params["embed"]
        if cfg.n_codebooks:
            logits = jnp.einsum("bsd,kvd->bskv", x, emb)
        else:
            logits = jnp.einsum("bsd,vd->bsv", x, emb)
    else:
        logits = L.dense(x, params["lm_head"])
        if cfg.n_codebooks:
            B, S = logits.shape[:2]
            logits = logits.reshape(B, S, cfg.n_codebooks, cfg.vocab_size)
    if cfg.final_logit_softcap:
        logits = L.softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits.astype(jnp.float32)


# ==========================================================================
# attention-family block (dense / moe / hybrid / vlm / audio)
# ==========================================================================

def _ffn(cfg: ModelConfig, p, x):
    if cfg.moe_experts:
        if cfg.moe_impl == "capacity":
            return L.moe_capacity(x, p["router"], p["wg"], p["wu"], p["wd"],
                                  cfg.moe_top_k)
        return L.moe_dense(x, p["router"], p["wg"], p["wu"], p["wd"],
                           cfg.moe_top_k)
    return L.swiglu(x, p["wg"], p["wu"], p["wd"])


def _qkv(cfg: ModelConfig, p, h):
    B, S, _ = h.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = L.dense(h, p["wq"], p.get("bq")).reshape(B, S, H, hd)
    k = L.dense(h, p["wk"], p.get("bk")).reshape(B, S, KV, hd)
    v = L.dense(h, p["wv"], p.get("bv")).reshape(B, S, KV, hd)
    return q, k, v


def _attn_block_seq(cfg: ModelConfig, p, x, window, positions, q_offset,
                    prefix_kv=None, seq_par: bool = False):
    """Full-sequence attention block (train / prefill).

    prefix_kv: optional (k, v) each (B, P, KV, hd) — the RAGCache document
    prefix pulled from the knowledge tree (already roped at absolute pos).
    Returns (out, (k_full, v_full)).
    """
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _qkv(cfg, p, h)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    if prefix_kv is not None:
        pk, pv = prefix_kv
        k_full = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        v_full = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
    else:
        k_full, v_full = k, v
    if seq_par and L.SEQ_PARALLEL_AXIS:
        o = L.flash_attention_seqpar(
            q, k_full, v_full, q_offset=q_offset, window=window,
            logit_cap=cfg.attn_logit_softcap, axis=L.SEQ_PARALLEL_AXIS)
        # store the per-layer cache hd-sharded: the stacked scan output is
        # otherwise batch-sharded only and dominates peak HBM at 32k
        from jax.sharding import PartitionSpec as _P
        if k_full.shape[-1] % 8 == 0:
            con = _P(None, None, None, L.SEQ_PARALLEL_AXIS)
            k_full = jax.lax.with_sharding_constraint(k_full, con)
            v_full = jax.lax.with_sharding_constraint(v_full, con)
    else:
        o = L.flash_attention(
            q, k_full, v_full,
            q_offset=q_offset, window=window,
            logit_cap=cfg.attn_logit_softcap,
        )
    B, S = x.shape[:2]
    o = L.dense_rowsum(o.reshape(B, S, -1), p["wo"])
    x = x + o
    if cfg.family == "hybrid":
        x = x + _ssm_branch_seq(cfg, p, x)[0]
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + _ffn(cfg, p, h2)
    return x, (k_full, v_full)


def _ssm_branch_seq(cfg: ModelConfig, p, x, state=None):
    B, S, D = x.shape
    H, hd, N = cfg.n_heads, cfg.hd, cfg.ssm_state
    h = L.rms_norm(x, p["ssm_ln"], cfg.norm_eps)
    xin = jax.nn.silu(L.dense(h, p["ssm_in"])).reshape(B, S, H, hd)
    delta = jax.nn.softplus(L.dense(h, p["ssm_dt"]).astype(jnp.float32))
    Bm = L.dense(h, p["ssm_B"])
    Cm = L.dense(h, p["ssm_C"])
    y, new_state = L.mamba_scan(xin, delta, p["ssm_A"], Bm, Cm, p["ssm_D"],
                                state=state)
    out = L.dense(y.reshape(B, S, H * hd), p["ssm_out"])
    return out, new_state


def _attn_block_decode(cfg: ModelConfig, p, x, window, pos, k_cache, v_cache,
                       ssm_state=None):
    """One-token decode block. pos: (B,) length *after* appending this token.
    k_cache/v_cache: (B, Smax, KV, hd). Returns out + updated caches."""
    B = x.shape[0]
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _qkv(cfg, p, h)                          # S == 1
    rope_pos = (pos - 1)[:, None]
    q = L.apply_rope(q, rope_pos, cfg.rope_theta)
    k = L.apply_rope(k, rope_pos, cfg.rope_theta)
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, pos - 1].set(k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, pos - 1].set(v[:, 0].astype(v_cache.dtype))
    o = L.decode_attention(q, k_cache, v_cache, pos=pos, window=window,
                           logit_cap=cfg.attn_logit_softcap)
    o = L.dense_rowsum(o.reshape(B, 1, -1), p["wo"])
    x = x + o
    new_ssm = None
    if cfg.family == "hybrid":
        y, new_ssm = _ssm_branch_seq(cfg, p, x, state=ssm_state)
        x = x + y
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + _ffn(cfg, p, h2)
    return x, k_cache, v_cache, new_ssm


# ==========================================================================
# xLSTM blocks
# ==========================================================================

def _mlstm_block(cfg: ModelConfig, p, x, state=None):
    """state: (C, n, m, conv_buf) or None. Returns (x_out, new_state)."""
    B, S, D = x.shape
    Dp = int(cfg.proj_factor * D)
    H = cfg.n_heads
    hd = Dp // H
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    up = L.dense(h, p["w_up"])
    xp, z = jnp.split(up, 2, axis=-1)                  # (B, S, Dp) each
    conv_buf = state[3] if state is not None else None
    xc, conv_buf = L.causal_conv1d(xp, p["conv_w"], conv_buf)
    xc = jax.nn.silu(xc)
    q = L.dense(xc, p["wq"]).reshape(B, S, H, hd)
    k = L.dense(xc, p["wk"]).reshape(B, S, H, hd)
    v = L.dense(xp, p["wv"]).reshape(B, S, H, hd)
    gif = L.dense(xc, p["w_if"], p["b_if"])            # (B, S, 2H)
    i_g, f_g = jnp.split(gif, 2, axis=-1)
    mstate = None if state is None else state[:3]
    if S == 1:
        hout, (C, n, m) = L.mlstm_scan(q, k, v, i_g, f_g, state=mstate)
    else:
        # chunkwise-parallel form: MXU matmuls intra-chunk, O(1) BPTT
        # residuals per chunk (docs/ARCHITECTURE.md §3)
        hout, (C, n, m) = L.mlstm_chunkwise(q, k, v, i_g, f_g, state=mstate)
    hout = hout.reshape(B, S, Dp)
    hout = L.rms_norm(hout, p["gn"], cfg.norm_eps)
    hout = hout * jax.nn.silu(z)
    return x + L.dense(hout, p["w_down"]), (C, n, m, conv_buf)


def _slstm_block(cfg: ModelConfig, p, x, state=None):
    B, S, D = x.shape
    H = cfg.n_heads
    hd = D // H
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    zifo = L.dense(h, p["w_x"], p["b_x"]).reshape(B, S, H, 4 * hd)
    out, new_state = L.slstm_scan(zifo, p["r_w"], state)
    out = out.reshape(B, S, D)
    out = L.rms_norm(out, p["gn"], cfg.norm_eps)
    x = x + out
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + L.swiglu(h2, p["wg"], p["wu"], p["wd"])
    return x, new_state


def _xlstm_init_state(cfg: ModelConfig, batch: int):
    D = cfg.d_model
    Dp = int(cfg.proj_factor * D)
    H = cfg.n_heads
    hd_m, hd_s = Dp // H, D // H
    K = cfg.conv_kernel

    def m_state(lead):
        return (
            jnp.zeros(lead + (batch, H, hd_m, hd_m), jnp.float32),
            jnp.zeros(lead + (batch, H, hd_m), jnp.float32),
            jnp.full(lead + (batch, H), L.NEG_INF, jnp.float32),
            jnp.zeros(lead + (batch, K - 1, Dp), cfg.jdtype),
        )

    def s_state(lead):
        return (
            jnp.zeros(lead + (batch, H, hd_s), jnp.float32),
            jnp.ones(lead + (batch, H, hd_s), jnp.float32),
            jnp.zeros(lead + (batch, H, hd_s), jnp.float32),
            jnp.zeros(lead + (batch, H, hd_s), jnp.float32),
        )

    if cfg.slstm_every > 0:
        period = cfg.slstm_every
        np_ = cfg.n_layers // period
        return {"mlstm": m_state((np_, period - 1)), "slstm": s_state((np_,))}
    return {"mlstm": m_state((cfg.n_layers,)), "slstm": None}


def _run_xlstm(cfg: ModelConfig, params, x, state):
    """Scan xLSTM blocks. state is the full stacked state pytree (required —
    use _xlstm_init_state for fresh). Returns (x, new_state)."""
    mblk = params["blocks"]["mlstm"]

    def m_layer(x, pst):
        p, st = pst
        x, st = _mlstm_block(cfg, p, x, st)
        return x, st

    if cfg.slstm_every > 0:
        sblk = params["blocks"]["slstm"]

        def period_body(x, xs):
            mp, mst, sp, sst = xs
            x, mst_new = lax.scan(m_layer, x, (mp, mst))
            x, sst_new = _slstm_block(cfg, sp, x, sst)
            return x, (mst_new, sst_new)

        x, (mst, sst) = lax.scan(
            period_body, x,
            (mblk, state["mlstm"], sblk, state["slstm"]),
        )
        return x, {"mlstm": mst, "slstm": sst}

    x, mst = lax.scan(m_layer, x, (mblk, state["mlstm"]))
    return x, {"mlstm": mst, "slstm": None}


# ==========================================================================
# public entry points
# ==========================================================================

def _layer_windows_arr(cfg: ModelConfig) -> jax.Array:
    return jnp.asarray(cfg.layer_windows(), jnp.int32)


def forward_hidden(cfg: ModelConfig, params, inputs: Dict[str, jax.Array],
                   *, remat: bool = False) -> jax.Array:
    """Training-path forward: full sequence, returns final hidden states."""
    x = embed_inputs(cfg, params, inputs)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    if cfg.family == "ssm":
        state = _xlstm_init_state(cfg, x.shape[0])
        x, _ = _run_xlstm(cfg, params, x, state)
        return x

    windows = _layer_windows_arr(cfg)

    def body(x, pw):
        p, w = pw
        out, _ = _attn_block_seq(cfg, p, x, w, positions, 0)
        return out, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, (params["blocks"], windows))
    return x


def forward(cfg: ModelConfig, params, inputs: Dict[str, jax.Array],
            *, remat: bool = False) -> jax.Array:
    return lm_logits(cfg, params,
                     forward_hidden(cfg, params, inputs, remat=remat))


def prefill(cfg: ModelConfig, params, inputs: Dict[str, jax.Array],
            prefix_cache=None, prefix_len: int = 0):
    """Serving prefill.  Returns (logits_last, cache).

    prefix_cache (RAGCache hook):
      attention families: {"k","v"} each (Lc, B, P, KV, hd)  (Lc = n_layers)
      ssm family:         stacked xLSTM state pytree (document state)
      hybrid:             {"k","v","ssm"}

    The returned cache holds the *full* sequence (prefix + new) so the
    controller can insert the new document nodes into the knowledge tree.
    """
    x = embed_inputs(cfg, params, inputs)
    B, S = x.shape[:2]

    if cfg.family == "ssm":
        state = prefix_cache if prefix_cache is not None else _xlstm_init_state(cfg, B)
        x, new_state = _run_xlstm(cfg, params, x, state)
        return lm_logits(cfg, params, x[:, -1:]), new_state

    positions = prefix_len + jnp.arange(S, dtype=jnp.int32)
    windows = _layer_windows_arr(cfg)

    if cfg.family == "hybrid":
        ssm0 = (prefix_cache["ssm"] if prefix_cache is not None
                else _hybrid_ssm_init(cfg, B))

        def body(x, xs):
            p, w, pk, pv, sst = xs
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            q, k, v = _qkv(cfg, p, h)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            if prefix_cache is not None:
                k_full = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
                v_full = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
            else:
                k_full, v_full = k, v
            o = L.flash_attention(q, k_full, v_full, q_offset=prefix_len,
                                  window=w, logit_cap=cfg.attn_logit_softcap)
            x = x + L.dense_rowsum(o.reshape(B, S, -1), p["wo"])
            y, sst_new = _ssm_branch_seq(cfg, p, x, state=sst)
            x = x + y
            h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + _ffn(cfg, p, h2)
            return x, (k_full, v_full, sst_new)

        if prefix_cache is not None:
            xs = (params["blocks"], windows, prefix_cache["k"],
                  prefix_cache["v"], ssm0)
        else:
            zk = jnp.zeros((cfg.n_layers, B, 0, cfg.n_kv_heads, cfg.hd), cfg.jdtype)
            xs = (params["blocks"], windows, zk, zk, ssm0)
        x, (ks, vs, ssm) = lax.scan(body, x, xs)
        return lm_logits(cfg, params, x[:, -1:]), {"k": ks, "v": vs, "ssm": ssm}

    def body(x, xs):
        p, w, pk, pv = xs
        out, (kf, vf) = _attn_block_seq(cfg, p, x, w, positions, prefix_len,
                                        prefix_kv=(pk, pv), seq_par=True)
        return out, (kf, vf)

    if prefix_cache is not None:
        xs = (params["blocks"], windows, prefix_cache["k"], prefix_cache["v"])
    else:
        zk = jnp.zeros((cfg.n_layers, B, 0, cfg.n_kv_heads, cfg.hd), cfg.jdtype)
        xs = (params["blocks"], windows, zk, zk)
    x, (ks, vs) = lax.scan(body, x, xs)
    return lm_logits(cfg, params, x[:, -1:]), {"k": ks, "v": vs}


def _hybrid_ssm_init(cfg: ModelConfig, batch: int):
    return jnp.zeros(
        (cfg.n_layers, batch, cfg.n_heads, cfg.hd, cfg.ssm_state), jnp.float32
    )


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Preallocated decode cache for serve_step (dense layout for dry-run)."""
    if cfg.family == "ssm":
        return _xlstm_init_state(cfg, batch)
    cache = {
        "k": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd),
                       cfg.jdtype),
        "v": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd),
                       cfg.jdtype),
    }
    if cfg.family == "hybrid":
        cache["ssm"] = _hybrid_ssm_init(cfg, batch)
    return cache


def paged_decode_step(cfg: ModelConfig, params, tokens, k_pages, v_pages,
                      tables, counts, starts, write_blk, write_slot, pos,
                      *, attn_impl: str | None = None, mesh=None):
    """One decode iteration straight against the paged pool (RAGCache's
    steady-state hot path: no dense (L, B, S, KV, hd) re-materialization).

    k_pages/v_pages: the ``PagedKVStore`` buffers, (L, n_blocks, KV, block,
    hd).  tables/counts/starts: (B, n_slots) per-request run descriptors
    (token-level slot mapping compressed to runs — see
    kernels/paged_attention.py for the contract).  write_blk/write_slot:
    (B,) page coordinates of the token being decoded — its KV is appended
    in place per layer BEFORE attention, one page write a row, and
    ``counts`` must already include it.  pos: (B,) sequence length
    *including* that token (same semantics as ``decode_step``).

    Returns (logits, k_pages, v_pages).  Attention families only —
    recurrent state cannot be paged per-block.

    mesh: tensor-parallel serving — forwarded to the attention dispatch
    (per-shard Pallas via shard_map; the jnp path ignores it and lets GSPMD
    partition the sharded-KV einsums itself).
    """
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError("paged decode requires per-token KV; "
                         "ssm/hybrid families use decode_step")
    from repro.kernels import ops

    x = embed_inputs(cfg, params, {"tokens": tokens})
    B = x.shape[0]
    windows = _layer_windows_arr(cfg)
    rope_pos = (pos - 1)[:, None]
    # each row writes its one token as a one-token chunk row
    wblk, wslot = write_blk[:, None], write_slot[:, None]
    one = jnp.ones((B,), jnp.int32)

    def body(carry, xs):
        x, kp, vp = carry
        p, w, li = xs
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, p, h)                          # S == 1
        q = L.apply_rope(q, rope_pos, cfg.rope_theta)
        k = L.apply_rope(k, rope_pos, cfg.rope_theta)
        with jax.named_scope("kv_write"):
            kp = write_pages(kp, li, wblk, wslot, one, k, 1)
            vp = write_pages(vp, li, wblk, wslot, one, v, 1)
        with jax.named_scope("attn"):
            o = ops.paged_decode_attention(
                q[:, 0], kp, vp, tables, counts, starts, pos - 1, li, w,
                logit_cap=cfg.attn_logit_softcap, impl=attn_impl, mesh=mesh)
        x = x + L.dense_rowsum(o.reshape(B, 1, -1), p["wo"])
        with jax.named_scope("mlp"):
            h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + _ffn(cfg, p, h2)
        return (x, kp, vp), None

    (x, k_pages, v_pages), _ = lax.scan(
        body, (x, k_pages, v_pages),
        (params["blocks"], windows, jnp.arange(cfg.n_layers)))
    with jax.named_scope("logits"):
        logits = lm_logits(cfg, params, x)
    return logits, k_pages, v_pages


def paged_prefill_step(cfg: ModelConfig, params, tokens, k_pages, v_pages,
                       tables, counts, starts, q_start, q_len, write_blk,
                       write_slot, *, attn_impl: str | None = None,
                       mesh=None, max_segments: int = 1):
    """One ragged prefill chunk computed straight against the paged pool —
    the prefill twin of ``paged_decode_step`` (no dense (L, B, S, KV, hd)
    gather, no per-chunk dense KV to re-page afterwards).

    tokens: (B, Sq) right-padded chunk token rows; row ``b`` holds
    ``q_len[b]`` valid tokens whose first sits at absolute position
    ``q_start[b]``.  k_pages/v_pages: the ``PagedKVStore`` buffers,
    (L, n_blocks, KV, block, hd).  tables/counts/starts: (B, n_slots) run
    descriptors covering the cached prefix PLUS this chunk's freshly
    allocated pages (counts include the chunk's own tokens — causal masking
    over absolute positions keeps later rows from seeing earlier garbage).
    write_blk/write_slot: (B, Sq) page coordinates for every chunk token —
    KV is written in place per layer BEFORE attention, a page at a time
    (``kvcache.paged.write_pages``); padding rows point at the store's
    scratch block, which no live run ever reads.  max_segments: the most
    segments one row's tokens cross, which bounds the pages a row writes
    (``max_write_runs``); 1 under a chunked splitter.  A row that writes
    more pages than the bound allows loses the excess, so a caller checks
    its rows against it (the runtime does, in ``_run_paged_rows``).

    Returns (logits, k_pages, v_pages) with logits (B, 1, V) taken at each
    row's LAST VALID token, so the final chunk's call yields the first-token
    logits directly.  Attention families only — recurrent state cannot be
    paged per-block.

    mesh: tensor-parallel serving — forwarded to the attention dispatch
    (per-shard Pallas via shard_map; the jnp path ignores it and lets GSPMD
    partition the sharded-KV einsums itself).
    """
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError("paged prefill requires per-token KV; "
                         "ssm/hybrid families use prefill")
    from repro.kernels import ops

    x = embed_inputs(cfg, params, {"tokens": tokens})
    B, Sq = tokens.shape
    windows = _layer_windows_arr(cfg)
    positions = q_start[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None]
    n_runs = max_write_runs(Sq, k_pages.shape[3], max_segments)

    def body(carry, xs):
        x, kp, vp = carry
        p, w, li = xs
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, p, h)                          # (B, Sq, ., hd)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        with jax.named_scope("kv_write"):
            kp = write_pages(kp, li, write_blk, write_slot, q_len, k, n_runs)
            vp = write_pages(vp, li, write_blk, write_slot, q_len, v, n_runs)
        with jax.named_scope("attn"):
            o = ops.paged_prefill_attention(
                q.transpose(0, 2, 1, 3), kp, vp, tables, counts, starts,
                q_start, q_len, li, w, logit_cap=cfg.attn_logit_softcap,
                impl=attn_impl, mesh=mesh)
        x = x + L.dense_rowsum(o.transpose(0, 2, 1, 3).reshape(B, Sq, -1),
                               p["wo"])
        with jax.named_scope("mlp"):
            h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + _ffn(cfg, p, h2)
        return (x, kp, vp), None

    (x, k_pages, v_pages), _ = lax.scan(
        body, (x, k_pages, v_pages),
        (params["blocks"], windows, jnp.arange(cfg.n_layers)))
    last = jnp.clip(q_len - 1, 0, Sq - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    with jax.named_scope("logits"):
        logits = lm_logits(cfg, params, x_last)
    return logits, k_pages, v_pages


def decode_step(cfg: ModelConfig, params, tokens, cache, pos):
    """One decode iteration.

    tokens: (B, 1) or (B, K, 1) for audio.  pos: (B,) sequence length
    *including* the token being decoded.  Returns (logits, new_cache).
    """
    inputs = {"tokens": tokens}
    x = embed_inputs(cfg, params, inputs)

    if cfg.family == "ssm":
        x, new_state = _run_xlstm(cfg, params, x, cache)
        return lm_logits(cfg, params, x), new_state

    windows = _layer_windows_arr(cfg)

    if cfg.family == "hybrid":
        def body(x, xs):
            p, w, kc, vc, sst = xs
            x, kc, vc, sst = _attn_block_decode(cfg, p, x, w, pos, kc, vc, sst)
            return x, (kc, vc, sst)

        x, (ks, vs, ssm) = lax.scan(
            body, x, (params["blocks"], windows, cache["k"], cache["v"],
                      cache["ssm"])
        )
        return lm_logits(cfg, params, x), {"k": ks, "v": vs, "ssm": ssm}

    def body(x, xs):
        p, w, kc, vc = xs
        x, kc, vc, _ = _attn_block_decode(cfg, p, x, w, pos, kc, vc)
        return x, (kc, vc)

    x, (ks, vs) = lax.scan(body, x, (params["blocks"], windows, cache["k"],
                                     cache["v"]))
    return lm_logits(cfg, params, x), {"k": ks, "v": vs}
