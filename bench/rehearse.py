"""Compile the cells' device programs for a described TPU v5e, without one.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py

For every configuration in BENCHMARK.json, through its reference module
(``run.load_reference``) and at its mixes' page size: both paged kernels
alone, the module's weight-making call, and (unless ``--no-steps``) the
whole paged prefill step for every row bucket and the decode step of the
program the module's fields describe, with the KV pool the configuration
sizes.  Prints the compiler's memory analysis
of each; nothing runs, so nothing here is a measurement.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH, os.path.join(BENCH, "traffic")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import run  # noqa: E402


def gb(n: float) -> str:
    return f"{n / 1e9:.3f} GB"


def report(name: str, compiled, keys) -> None:
    m = compiled.memory_analysis()
    kernels = [k for k in keys if k in compiled.as_text()]
    print(f"{name}: arguments {gb(m.argument_size_in_bytes)}, outputs "
          f"{gb(m.output_size_in_bytes)}, temporaries {gb(m.temp_size_in_bytes)}, "
          f"aliased {gb(m.alias_size_in_bytes)}; kernels {kernels}", flush=True)


def main(argv=None, root: str = ROOT) -> None:
    """Rehearse the configurations of the checkout at ``root``."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--no-steps", action="store_true", help="kernels and weights only")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    from repro.kernels import paged_attention, paged_prefill
    from repro.models import model as M

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    bench = run.load_json(os.path.join(root, "BENCHMARK.json"))
    for c in bench["configs"]:
        conf = run.load_json(os.path.join(root, c["file"]))
        ref = run.load_reference(root, conf)
        keys = run.trace_keys(ref)
        w = ref.widths(conf)
        mixes = {wl["traffic"] for wl in bench["workloads"] if wl["config"] == c["name"]}
        mix = run.load_json(os.path.join(root, "bench", "traffic", f"{sorted(mixes)[0]}.json"))
        eng = mix["engine"]
        page, B = eng["block_size"], eng["max_batch"]
        n_blocks = conf["serving"]["pool_bytes"] // (page * run.kv_bytes_per_token(w))
        max_ctx = mix["top_k"] * mix["max_tokens"] + mix["question_tokens"] + mix["max_new_tokens"]
        T = -(-max_ctx // page) + 1 + mix["top_k"] + 1
        pool = sds((w.L, n_blocks, w.KV, page, w.hd), jnp.bfloat16)
        print(f"== {c['name']}: pool {n_blocks} pages of {page} ({gb(2 * pool.size * 2)}), "
              f"table width {T}", flush=True)
        # kernels alone
        q = sds((B, w.H, w.hd), jnp.bfloat16)
        dec = jax.jit(functools.partial(paged_attention.paged_decode_attention))
        report(f"paged_decode B={B}", dec.lower(q, pool, pool, sds((B, T)), sds((B, T)),
                                                  sds((B, T)), sds((B,)), 0, 0).compile(), keys)
        rows = sorted(run.expected_prefill_rows(mix))
        for sq in rows:
            q = sds((1, w.H, sq, w.hd), jnp.bfloat16)
            pre = jax.jit(paged_prefill.paged_prefill_attention)
            report(f"paged_prefill 1x{sq}", pre.lower(
                q, pool, pool, sds((1, T)), sds((1, T)), sds((1, T)), sds((1,)),
                sds((1,)), 0, 0).compile(), keys)
        # weights
        base = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)
        make = jax.jit(functools.partial(ref.make_params, w))
        report("weights", make.lower(base).compile(), keys)
        if args.no_steps:
            continue
        cfg = run.program_config(conf, ref, w)
        params = jax.eval_shape(functools.partial(ref.make_params, w), jax.random.key(0))
        params = jax.tree.map(lambda s: sds(s.shape, s.dtype), params)
        pre_step = jax.jit(
            lambda p, toks, tb, cn, sts, qs, ql, wb, ws, kp, vp:
            M.paged_prefill_step(cfg, p, toks, kp, vp, tb, cn, sts, qs, ql, wb, ws,
                                 attn_impl="pallas"),
            donate_argnums=(9, 10))
        for sq in rows:
            report(f"prefill step 1x{sq}", pre_step.lower(
                params, sds((1, sq)), sds((1, T)), sds((1, T)), sds((1, T)), sds((1,)),
                sds((1,)), sds((1, sq)), sds((1, sq)), pool, pool).compile(), keys)
        dec_step = jax.jit(
            lambda p, toks, tb, cn, sts, pos, wb, ws, kp, vp:
            M.paged_decode_step(cfg, p, toks, kp, vp, tb, cn, sts, wb, ws, pos,
                                attn_impl="pallas"),
            donate_argnums=(8, 9))
        report(f"decode step B={B}", dec_step.lower(
            params, sds((B, 1)), sds((B, T)), sds((B, T)), sds((B, T)), sds((B,)),
            sds((B,)), sds((B,)), pool, pool).compile(), keys)


if __name__ == "__main__":
    main()
