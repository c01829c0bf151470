"""Jit'd public wrappers for the Pallas kernels.

On a TPU runtime these dispatch to the compiled kernels; on CPU they run in
interpret mode (or, for the paged kernels, the pure-jnp twin), which
executes the kernel logic off the chip.  On a TPU the paged dispatch never
takes the jnp twin: a serving step there either runs the compiled kernel or
fails.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import prefix_attention as _pa
from repro.kernels import paged_attention as _pg
from repro.kernels import paged_prefill as _pp


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _paged_impl(impl: str | None) -> str:
    """Resolve a paged-kernel ``impl``: None -> "pallas" on TPU, "jnp"
    elsewhere.  The jnp twin is the CPU execution path only — asking for it
    on a TPU would hide the kernel the chip is meant to run."""
    if impl is None:
        return "pallas" if _on_tpu() else "jnp"
    if impl not in ("pallas", "interpret", "jnp"):
        raise ValueError(f"unknown paged-attention impl {impl!r}")
    if impl == "jnp" and _on_tpu():
        raise ValueError("the jnp paged-attention path is the CPU execution "
                         "path; on TPU the compiled Pallas kernel runs")
    return impl


@functools.partial(jax.jit, static_argnames=("prefix_len", "window",
                                             "block_q", "block_k",
                                             "interpret"))
def prefix_attention(q, k, v, *, prefix_len: int, window: int = 0,
                     block_q: int = 128, block_k: int = 128,
                     interpret: bool | None = None):
    """Flash prefill over dense [cached prefix ‖ new] KV (the A/B baseline;
    the paged engine uses ``paged_prefill_attention``). Layouts:
    q: (B, H, Sq, hd); k/v: (B, KV, prefix_len + Sq, hd)."""
    interp = (not _on_tpu()) if interpret is None else interpret
    return _pa.prefix_flash_attention(q, k, v, prefix_len=prefix_len,
                                      window=window, block_q=block_q,
                                      block_k=block_k, interpret=interp)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_tables, lengths,
                    *, interpret: bool | None = None):
    """Decode attention over paged KV. q: (B, H, hd)."""
    interp = (not _on_tpu()) if interpret is None else interpret
    return _pg.paged_attention(q, k_pages, v_pages, block_tables, lengths,
                               interpret=interp)


def paged_decode_attention(q, k_pages, v_pages, tables, counts, starts, qpos,
                           layer, window, *, logit_cap: float = 0.0,
                           impl: str | None = None, mesh=None,
                           axis: str = "model"):
    """Decode attention straight from the pool's layer-major page arrays
    (the serving runtime's steady-state hot path; see paged_attention.py for
    the run/slot-mapping contract).  Dispatch:

      impl=None        -> compiled Pallas kernel on TPU, pure-jnp per-page
                          online softmax elsewhere (the CPU execution path)
      impl="pallas"    -> force the compiled kernel
      impl="interpret" -> Pallas kernel body in interpret mode (tests: runs
                          the BlockSpec/grid logic bit-for-bit on CPU)
      impl="jnp"       -> force the jnp path (refused on TPU)

    Not jit-wrapped: this is called per-layer inside the (already jitted)
    decode step's layer scan, where ``layer``/``window`` are traced values.

    ``mesh``: tensor-parallel serving (serving/runtime.py ``--tp N``).  The
    jnp path ignores it — GSPMD partitions the per-head einsums along the
    sharded KV dim on its own.  The Pallas kernel cannot be auto-partitioned
    (pallas_call is opaque to the SPMD partitioner), so the pallas/interpret
    paths dispatch the kernel PER SHARD via ``shard_map``: each device runs
    the unchanged kernel over its local head tile — q sharded on heads, the
    pool planes on KV heads — with head-local block tables (the run tables
    are head-independent, hence replicated verbatim onto every shard).
    """
    impl = _paged_impl(impl)
    if impl == "jnp":
        return _pg.paged_decode_jnp(q, k_pages, v_pages, tables, counts,
                                    starts, qpos, layer, window,
                                    logit_cap=logit_cap)
    if mesh is not None and mesh.shape.get(axis, 1) > 1:
        return _paged_decode_sharded(q, k_pages, v_pages, tables, counts,
                                     starts, qpos, layer, window,
                                     logit_cap=logit_cap,
                                     interpret=impl == "interpret",
                                     mesh=mesh, axis=axis)
    return _pg.paged_decode_attention(q, k_pages, v_pages, tables, counts,
                                      starts, qpos, layer, window,
                                      logit_cap=logit_cap,
                                      interpret=impl == "interpret")


def _paged_decode_sharded(q, k_pages, v_pages, tables, counts, starts, qpos,
                          layer, window, *, logit_cap: float, interpret: bool,
                          mesh, axis: str):
    """Per-shard Pallas dispatch: grid shrinks to the shard's H/tp heads and
    the shard's (KV/tp)-head pool plane; no collectives — decode attention
    is embarrassingly parallel over heads (the later wo matmul's all-reduce
    belongs to the surrounding GSPMD program)."""
    from jax.sharding import PartitionSpec as P

    def local(q_l, kp_l, vp_l, tb, cn, st, qp, li, w):
        return _pg.paged_decode_attention(q_l, kp_l, vp_l, tb, cn, st, qp,
                                          li, w, logit_cap=logit_cap,
                                          interpret=interpret)

    rep2 = P(None, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis, None),
                  P(None, None, axis, None, None),
                  P(None, None, axis, None, None),
                  rep2, rep2, rep2, P(None), P(), P()),
        out_specs=P(None, axis, None), check_vma=False)
    return fn(q, k_pages, v_pages, tables, counts, starts, qpos,
              jnp.asarray(layer, jnp.int32), jnp.asarray(window, jnp.int32))


def paged_prefill_attention(q, k_pages, v_pages, tables, counts, starts,
                            q_start, q_len, layer, window, *,
                            logit_cap: float = 0.0, impl: str | None = None,
                            mesh=None, axis: str = "model"):
    """Ragged prefill attention straight from the pool's layer-major page
    arrays — the prefill twin of ``paged_decode_attention``, same dispatch
    table (None -> pallas on TPU / jnp on CPU; "pallas" / "interpret" /
    "jnp" to force), same run-table contract, plus the per-request
    ``q_start``/``q_len`` query-row contract (see paged_prefill.py).

    Not jit-wrapped: called per-layer inside the (already jitted) prefill
    step's layer scan, where ``layer``/``window`` are traced values.

    ``mesh``: as for decode — jnp partitions via GSPMD on its own; the
    pallas/interpret paths dispatch the kernel per shard over head-local
    tiles with replicated run tables.
    """
    impl = _paged_impl(impl)
    if impl == "jnp":
        return _pp.paged_prefill_jnp(q, k_pages, v_pages, tables, counts,
                                     starts, q_start, q_len, layer, window,
                                     logit_cap=logit_cap)
    if mesh is not None and mesh.shape.get(axis, 1) > 1:
        return _paged_prefill_sharded(q, k_pages, v_pages, tables, counts,
                                      starts, q_start, q_len, layer, window,
                                      logit_cap=logit_cap,
                                      interpret=impl == "interpret",
                                      mesh=mesh, axis=axis)
    return _pp.paged_prefill_attention(q, k_pages, v_pages, tables, counts,
                                       starts, q_start, q_len, layer, window,
                                       logit_cap=logit_cap,
                                       interpret=impl == "interpret")


def _paged_prefill_sharded(q, k_pages, v_pages, tables, counts, starts,
                           q_start, q_len, layer, window, *, logit_cap: float,
                           interpret: bool, mesh, axis: str):
    """Per-shard Pallas dispatch for prefill: identical scheme to
    ``_paged_decode_sharded`` with the extra Sq query axis riding along
    unsharded — prefill attention is embarrassingly parallel over heads."""
    from jax.sharding import PartitionSpec as P

    def local(q_l, kp_l, vp_l, tb, cn, st, qs, ql, li, w):
        return _pp.paged_prefill_attention(q_l, kp_l, vp_l, tb, cn, st, qs,
                                           ql, li, w, logit_cap=logit_cap,
                                           interpret=interpret)

    rep2 = P(None, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis, None, None),
                  P(None, None, axis, None, None),
                  P(None, None, axis, None, None),
                  rep2, rep2, rep2, P(None), P(None), P(), P()),
        out_specs=P(None, axis, None, None), check_vma=False)
    return fn(q, k_pages, v_pages, tables, counts, starts, q_start, q_len,
              jnp.asarray(layer, jnp.int32), jnp.asarray(window, jnp.int32))
