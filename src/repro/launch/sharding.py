"""Partition rules: params, optimizer state, inputs, and decode caches.

Megatron-style tensor parallelism over the ``model`` axis:
  column-parallel: wq/wk/wv (fused head dim), wg/wu (d_ff), router, embeddings
  row-parallel:    wo, wd (contracting dim)
  expert-parallel: MoE expert stacks shard their expert dim over ``model``
                   when divisible, else fall back to d_ff sharding.
Optimizer moments additionally shard one more dim over the data axes
(ZeRO-1), which is what lets 34B-params x fp32 x 2 moments fit v5e HBM.

Every rule checks divisibility and falls back to replication — the dry-run
must lower for all 10 architectures x 4 shapes, including awkward head
counts (qwen2's 14 heads, hymba's 25).
"""
from __future__ import annotations

from typing import Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import data_axes
from repro.models.config import ModelConfig

# param-name -> (shard_dim_from_end, kind)
#   kind "col": shard the output dim; "row": shard the contracting dim.
_COL = {"wq", "wk", "wv", "wg", "wu", "w_up", "w_x", "router",
        "ssm_in", "lm_head", "vision_proj"}
_ROW = {"wo", "wd", "w_down", "ssm_out"}
_ATTN = {"wq", "wk", "wv", "wo"}

# §Perf optimization: when a head count doesn't tile the model axis (yi's 56
# heads / qwen2's 14 / hymba's 25 over 16), GSPMD falls back to sharding the
# *contracting* hd dim of attention, turning every flash-chunk score matmul
# into an all-reduce (measured: 93% of yi-34b prefill collective bytes).
# With this flag, such archs replicate attention weights over `model` and run
# attention purely data-parallel; FFN/vocab stay tensor-parallel.
ATTN_REPLICATE_IF_RAGGED = False


def _heads_tile_cleanly(cfg: ModelConfig, msize: int) -> bool:
    """True if a fused (H*hd) sharding is expressible as whole heads or an
    even intra-head split (GSPMD can propagate through the reshape)."""
    for heads in (cfg.n_heads, cfg.n_kv_heads):
        per_shard = heads * cfg.hd // msize
        if per_shard == 0:
            return False
        if per_shard % cfg.hd != 0 and cfg.hd % per_shard != 0:
            return False
    return True


def kv_heads_shardable(cfg: ModelConfig, tp: int) -> bool:
    """True when a ``tp``-way model axis splits attention into WHOLE heads:
    the paged pool's ``(L, n_blocks, KV, block, hd)`` planes shard dim 2, so
    a KV head split *across* devices would tear a page's head tile apart
    (and break the per-shard kernel dispatch's head-local block tables)."""
    return (tp >= 1 and cfg.n_kv_heads % tp == 0 and cfg.n_heads % tp == 0)


def assert_tp_compatible(cfg: ModelConfig, tp: int) -> None:
    """Error EARLY (before any mesh/device work) on a mesh/model pair that
    would shard a KV head across devices.  ``param_spec`` itself falls back
    to replication for awkward head counts — silently correct for dense
    training, but the serving pool cannot fall back: its layout IS the head
    dim.  Raising here turns a latent wrong-layout run into a one-line
    ``serve.py --tp`` error."""
    if tp > 1 and not kv_heads_shardable(cfg, tp):
        raise ValueError(
            f"--tp {tp} would shard a KV head across devices: {cfg.name} has "
            f"{cfg.n_heads} query / {cfg.n_kv_heads} KV heads, and the paged "
            f"pool shards whole KV heads over the model axis.  Pick tp "
            f"dividing both head counts "
            f"(e.g. {_clean_tps(cfg)}).")


def _clean_tps(cfg: ModelConfig, limit: int = 8) -> list:
    return [t for t in range(1, limit + 1)
            if kv_heads_shardable(cfg, t)]


def spec_summary(cfg: ModelConfig, mesh: Mesh, params_shape) -> str:
    """One-line-per-rule summary of the CHOSEN partition specs — surfaces
    the silent ``param_spec`` fallbacks (ragged heads, non-divisible d_ff /
    experts) that otherwise only show up as replicated HLO.  Printed by
    ``launch/dryrun.py`` and by ``serve.py --tp`` so the operator sees what
    actually sharded."""
    msize = _axis_size(mesh, "model")
    lines = [f"partition specs over model={msize} "
             f"(fused heads tile cleanly: "
             f"{_heads_tile_cleanly(cfg, msize)}; whole-KV-head serving "
             f"split: {kv_heads_shardable(cfg, msize)}):"]
    seen = {}
    leaves = jax.tree_util.tree_leaves_with_path(params_shape)
    for path, leaf in leaves:
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path)
        spec = param_spec(keys, leaf, cfg, mesh)
        name = keys[-1]
        sharded = any(s is not None for s in spec)
        label = f"{spec}" if sharded else "replicated"
        if name not in seen:
            seen[name] = label
        elif seen[name] != label:
            seen[name] += f" | {label}"
    for name in sorted(seen):
        lines.append(f"  {name:12s} -> {seen[name]}")
    return "\n".join(lines)


def pool_kv_spec() -> P:
    """Paged-pool partition spec: ``(L, n_blocks, KV, block, hd)`` shards
    whole KV heads over ``model``; block geometry stays replicated (block
    tables / slot mappings are identical on every shard)."""
    return P(None, None, "model", None, None)


def serving_param_shardings(cfg: ModelConfig, params_shape, mesh: Mesh):
    """``param_shardings`` minus row parallelism: the serving engine's
    deterministic-TP mode (models/layers.py::tp_deterministic).

    ``wo``/``wd`` REPLICATE instead of sharding their contraction rows.
    Row-parallel matmuls lower to per-device partial sums + all-reduce,
    whose float accumulation order differs from the single-device matmul —
    logits then drift a few ulps per layer and near-tie argmaxes flip
    greedy tokens between mesh sizes.  With the row matrices replicated
    AND ``dense_rowsum`` gathering the sharded activations first, every
    contraction is computed whole on each device: serving stays
    bit-identical at tp 1/2/4 (the --check-tokens contract) at the cost of
    not sharding the two down-projections.  Training keeps full Megatron
    row parallelism via ``param_shardings``."""
    def spec(path, leaf):
        if path and path[-1] in ("wo", "wd"):
            return P()
        return param_spec(path, leaf, cfg, mesh)
    return tree_shardings(params_shape, spec, mesh)


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def _fits(dim: int, size: int) -> bool:
    return dim % size == 0 and dim >= size


def param_spec(path: Tuple[str, ...], leaf, cfg: ModelConfig, mesh: Mesh) -> P:
    name = path[-1]
    ndim = leaf.ndim
    msize = _axis_size(mesh, "model")
    spec = [None] * ndim

    is_expert = (cfg.moe_experts > 0 and name in ("wg", "wu", "wd")
                 and "blocks" in path)
    if is_expert:
        # (L, E, D, F) / (L, E, F, D): expert-parallel when E % model == 0
        if _fits(cfg.moe_experts, msize):
            spec[1] = "model"
        else:
            d = ndim - 1 if name in ("wg", "wu") else ndim - 2
            if _fits(leaf.shape[d], msize):
                spec[d] = "model"
        return P(*spec)

    if (ATTN_REPLICATE_IF_RAGGED and name in _ATTN
            and cfg.family != "ssm"
            and not _heads_tile_cleanly(cfg, msize)):
        # ragged heads: attention runs data-parallel (+ seq-parallel flash);
        # its weights shard over the *data* axes (ZeRO-style) and are
        # gathered once per layer — 16x less HBM than replication, and far
        # cheaper than the per-chunk score all-reduces of hd-sharding.
        daxes = data_axes(mesh)
        dsize = int(np.prod([_axis_size(mesh, a) for a in daxes]))
        ax = daxes if len(daxes) > 1 else daxes[0]
        d = ndim - 1 if name != "wo" else ndim - 2
        if _fits(leaf.shape[d], dsize):
            spec[d] = ax
        return P(*spec)

    if name == "embed":
        # (V, D) or (K, V, D): shard vocab
        d = ndim - 2
        if _fits(leaf.shape[d], msize):
            spec[d] = "model"
        return P(*spec)
    if name in _COL:
        d = ndim - 1
        if _fits(leaf.shape[d], msize):
            spec[d] = "model"
        return P(*spec)
    if name in _ROW:
        d = ndim - 2
        if _fits(leaf.shape[d], msize):
            spec[d] = "model"
        return P(*spec)
    return P()  # norms, biases, gates, conv, recurrent mats: replicate


def opt_spec(pspec: P, leaf, mesh: Mesh) -> P:
    """ZeRO-1: moments take the param spec + one extra dim over data axes."""
    daxes = data_axes(mesh)
    dsize = int(np.prod([_axis_size(mesh, a) for a in daxes]))
    spec = list(pspec) + [None] * (leaf.ndim - len(pspec))
    for d in range(leaf.ndim):
        if spec[d] is None and _fits(leaf.shape[d], dsize):
            spec[d] = daxes if len(daxes) > 1 else daxes[0]
            break
    return P(*spec)


def tree_shardings(tree, spec_fn, mesh: Mesh):
    def one(path, leaf):
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path)
        return NamedSharding(mesh, spec_fn(keys, leaf))
    return jax.tree_util.tree_map_with_path(one, tree)


def param_shardings(cfg: ModelConfig, params_shape, mesh: Mesh):
    return tree_shardings(
        params_shape, lambda p, l: param_spec(p, l, cfg, mesh), mesh)


def opt_shardings(cfg: ModelConfig, params_shape, opt_shape, mesh: Mesh):
    """AdamWState(step, m, v) shardings."""
    def m_spec(path, leaf):
        # path starts with 'm'/'v' field then mirrors param path
        ps = param_spec(path, leaf, cfg, mesh)
        return opt_spec(ps, leaf, mesh)
    step_sh = NamedSharding(mesh, P())
    m_sh = tree_shardings(opt_shape.m, m_spec, mesh)
    v_sh = tree_shardings(opt_shape.v, m_spec, mesh)
    return type(opt_shape)(step=step_sh, m=m_sh, v=v_sh)


# --------------------------------------------------------------------------
# activations / inputs / caches
# --------------------------------------------------------------------------

def batch_spec(cfg: ModelConfig, batch: int, mesh: Mesh) -> Tuple:
    daxes = data_axes(mesh)
    dsize = int(np.prod([_axis_size(mesh, a) for a in daxes]))
    ax = daxes if len(daxes) > 1 else daxes[0]
    return (ax if _fits(batch, dsize) else None), dsize


def input_shardings(cfg: ModelConfig, inputs, mesh: Mesh):
    def spec(path, leaf):
        if leaf.ndim == 0:
            return P()
        bax, _ = batch_spec(cfg, leaf.shape[0], mesh)
        return P(bax, *([None] * (leaf.ndim - 1)))
    return tree_shardings(inputs, lambda p, l: spec(p, l), mesh)


def cache_shardings(cfg: ModelConfig, cache, mesh: Mesh, batch: int):
    """Decode-cache shardings.

    KV cache (L, B, S, KV, hd): batch over data axes when divisible; for
    batch=1 long-context decode, the *sequence* dim shards over the data axes
    instead (distributed-context decode); hd over model (hd % 16 == 0 for
    every assigned arch).
    """
    daxes = data_axes(mesh)
    dsize = int(np.prod([_axis_size(mesh, a) for a in daxes]))
    msize = _axis_size(mesh, "model")
    ax = daxes if len(daxes) > 1 else daxes[0]
    batch_ok = _fits(batch, dsize)

    def spec(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        nd = leaf.ndim
        if name in ("k", "v"):                    # (L, B, S, KV, hd)
            s = [None] * nd
            if batch_ok:
                s[1] = ax
            elif _fits(leaf.shape[2], dsize):
                s[2] = ax                          # shard sequence
            if _fits(leaf.shape[4], msize):
                s[4] = "model"
            return P(*s)
        if name == "ssm":                          # (L, B, H, hd, N)
            s = [None] * nd
            if batch_ok:
                s[1] = ax
            if _fits(leaf.shape[3], msize):
                s[3] = "model"
            return P(*s)
        # xLSTM states: (..., B, H, hd[, hd]) / conv (..., B, K-1, Dp)
        s = [None] * nd
        for d in range(nd):
            if batch_ok and leaf.shape[d] == batch and s[d] is None:
                s[d] = ax
                break
        # shard the largest remaining dim over model if divisible
        order = sorted(range(nd), key=lambda d: -leaf.shape[d])
        for d in order:
            if s[d] is None and _fits(leaf.shape[d], msize) \
                    and leaf.shape[d] >= 4 * msize:
                s[d] = "model"
                break
        return P(*s)

    return tree_shardings(cache, lambda p, l: spec(p, l), mesh)
