"""Compile the paged Pallas kernels for a TPU v5e chip that is described,
not attached.

Interpret mode runs the kernel bodies on the CPU but cannot see what the TPU
compiler refuses: block tiles off the (8, 128) grid, VMEM overruns, ops
Mosaic cannot lower.  These tests compile both paged kernels for one chip of
a ``v5e:2x2`` topology at the widths the chip serves — qwen2-0.5b, and
llama2-7b's per-shard widths at tp=4 — and check that the compiled program
holds the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, so under several test
workers only the worker that runs this file loads it.
"""

import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_attention, paged_prefill

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
