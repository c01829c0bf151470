"""Compile the paged Pallas kernels for a TPU v5e chip that is described,
not attached.

Interpret mode runs the kernel bodies on the CPU but cannot see what the TPU
compiler refuses: block tiles off the (8, 128) grid, VMEM overruns, ops
Mosaic cannot lower.  These tests compile both paged kernels for one chip of
a ``v5e:2x2`` topology at the widths the chip serves — qwen2-0.5b, and
llama2-7b's per-shard widths at tp=4 — and check that the compiled program
holds the kernel (``tpu_custom_call``).  They also compile the whole paged
prefill and decode steps of a two-layer model at qwen2-0.5b's widths and
check that the KV write inside the layer loop leaves the pool in place: no
copy of the whole pool inside the loop's body.  Nothing runs.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, so under several test
workers only the worker that runs this file loads it.
"""

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import paged_attention, paged_prefill
from repro.models import model as M

# (query heads, KV heads, head dim) of one chip's share
WIDTHS = {
    "qwen2-0.5b": (14, 2, 64),
    "llama2-7b/tp4": (8, 8, 128),
}
LAYERS, N_PAGES, PAGE, BATCH, SLOTS = 24, 64, 16, 4, 8
# query rows per request: 0 = one decode token, else a prefill chunk bucket
ROWS = {"decode": 0, "prefill8": 8, "prefill128": 128}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip, so keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    with _no_persistent_cache():
        return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("step", list(ROWS))
@pytest.mark.parametrize("width", list(WIDTHS))
def test_paged_kernel_compiles_for_v5e(one_chip, width, step):
    H, KV, hd = WIDTHS[width]
    rows = ROWS[step]

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = spec((LAYERS, N_PAGES, KV, PAGE, hd), jnp.bfloat16)
    table = spec((BATCH, SLOTS))
    scalar = spec(())
    if rows == 0:
        fn, name = paged_attention.paged_decode_attention, "paged_decode"
        args = [spec((BATCH, H, hd), jnp.bfloat16), pages, pages]
        args += [table, table, table, spec((BATCH,))]
    else:
        fn, name = paged_prefill.paged_prefill_attention, "paged_prefill"
        args = [spec((BATCH, H, rows, hd), jnp.bfloat16), pages, pages]
        args += [table, table, table, spec((BATCH,)), spec((BATCH,))]
    text = _compiled_text(fn, *args, scalar, scalar)
    assert "tpu_custom_call" in text
    assert name in text


# the whole step: prefill buckets of the benchmark's mix, and decode
STEPS = {"prefill8": 8, "prefill512": 512, "decode": 0}
STEP_PAGES, STEP_PAGE = 40, 128


def _loop_computations(text: str) -> dict:
    """Instruction lines of every computation a ``while`` body reaches."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.startswith(" "):
            comps[name].append(line)
    callee = re.compile(r"(?:calls|body|condition|to_apply|"
                        r"branch_computations)=\{?([%\w.\-, ]+)\}?")
    todo = [m.group(1) for lines in comps.values() for line in lines
            for m in re.finditer(r"body=(%[\w.\-]+)", line)]
    seen = {}
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen[c] = comps[c]
        for line in comps[c]:
            for m in callee.finditer(line):
                todo += [n.strip() for n in m.group(1).split(",")]
    return seen


@pytest.mark.parametrize("step", list(STEPS))
def test_step_kv_write_copies_no_pool_in_the_layer_loop(one_chip, step):
    """Each layer's KV write must update the pool in its own layout: a
    token-level scatter there made the compiler copy the whole pool into
    another tiling and back, once per layer and plane."""
    cfg = dataclasses.replace(get_config("qwen2-0.5b"), n_layers=2)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: spec(a.shape, a.dtype), params)
    pool_shape = (cfg.n_layers, STEP_PAGES, cfg.n_kv_heads, STEP_PAGE, cfg.hd)
    pool = spec(pool_shape, jnp.bfloat16)
    rows = STEPS[step]
    if rows:
        def fn(p, toks, tb, cn, sts, qs, ql, wb, ws, kp, vp):
            return M.paged_prefill_step(cfg, p, toks, kp, vp, tb, cn, sts,
                                        qs, ql, wb, ws, attn_impl="pallas")
        B, kernel = 1, "paged_prefill"
        args = [spec((B, rows))] + [spec((B, SLOTS))] * 3
        args += [spec((B,)), spec((B,)), spec((B, rows)), spec((B, rows))]
        donate = (9, 10)
    else:
        def fn(p, toks, tb, cn, sts, pos, wb, ws, kp, vp):
            return M.paged_decode_step(cfg, p, toks, kp, vp, tb, cn, sts, wb,
                                       ws, pos, attn_impl="pallas")
        B, kernel = BATCH, "paged_decode"
        args = [spec((B, 1))] + [spec((B, SLOTS))] * 3
        args += [spec((B,))] * 3
        donate = (8, 9)
    with _no_persistent_cache():
        text = jax.jit(fn, donate_argnums=donate).lower(
            params, *args, pool, pool).compile().as_text()
    assert "tpu_custom_call" in text and kernel in text
    loop = _loop_computations(text)
    assert loop, "no layer loop found in the compiled step"
    pool_type = "bf16[" + ",".join(map(str, pool_shape)) + "]"
    copies = [line.strip()[:120] for lines in loop.values() for line in lines
              if re.search(r"= " + re.escape(pool_type) + r"\S* copy(-start)?\(",
                           line)]
    assert not copies, copies
