"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# interpret-mode parity sweeps are minutes-scale: the CI `kernels` lane
# runs this file on every push/PR; the fast lane skips it (slow marker)
pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,H,KV,Sq,P,hd", [
    (1, 4, 2, 16, 32, 32),
    (2, 8, 8, 24, 40, 64),
    (1, 4, 1, 32, 0, 32),      # MQA, no prefix
    (2, 2, 2, 8, 8, 128),      # MHA
    (1, 6, 2, 17, 23, 32),     # ragged sizes (padding paths)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefix_attention_sweep(B, H, KV, Sq, P, hd, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, H, Sq, hd), dtype)
    k = jax.random.normal(k2, (B, KV, P + Sq, hd), dtype)
    v = jax.random.normal(k3, (B, KV, P + Sq, hd), dtype)
    out = ops.prefix_attention(q, k, v, prefix_len=P, block_q=8, block_k=8,
                               interpret=True)
    want = ref.reference_prefix_attention(q, k, v, prefix_len=P)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("window", [8, 24])
def test_prefix_attention_sliding_window(window):
    k1, k2, k3 = jax.random.split(KEY, 3)
    B, H, KV, Sq, P, hd = 2, 4, 2, 16, 32, 32
    q = jax.random.normal(k1, (B, H, Sq, hd))
    k = jax.random.normal(k2, (B, KV, P + Sq, hd))
    v = jax.random.normal(k3, (B, KV, P + Sq, hd))
    out = ops.prefix_attention(q, k, v, prefix_len=P, window=window,
                               block_q=8, block_k=8, interpret=True)
    want = ref.reference_prefix_attention(q, k, v, prefix_len=P,
                                          window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


def test_prefix_attention_matches_model_flash():
    """Kernel, pure-jnp flash, and naive oracle all agree."""
    from repro.models import layers as L
    k1, k2, k3 = jax.random.split(KEY, 3)
    B, H, KV, Sq, P, hd = 1, 4, 2, 16, 16, 32
    q = jax.random.normal(k1, (B, Sq, H, hd))
    k = jax.random.normal(k2, (B, P + Sq, KV, hd))
    v = jax.random.normal(k3, (B, P + Sq, KV, hd))
    flash = L.flash_attention(q, k, v, q_offset=P, q_chunk=8, kv_chunk=8)
    kern = ops.prefix_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), prefix_len=P, block_q=8, block_k=8,
        interpret=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(kern), atol=1e-4)


@pytest.mark.parametrize("B,H,KV,hd,page,npages,nslots", [
    (2, 4, 2, 32, 8, 16, 4),
    (1, 8, 8, 64, 16, 8, 3),
    (3, 4, 4, 128, 8, 32, 6),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, H, KV, hd, page, npages, nslots, dtype):
    k1, k2, k3, k4, k5 = jax.random.split(KEY, 5)
    q = jax.random.normal(k1, (B, H, hd), dtype)
    kp = jax.random.normal(k2, (npages, KV, page, hd), dtype)
    vp = jax.random.normal(k3, (npages, KV, page, hd), dtype)
    bt = jax.random.randint(k4, (B, nslots), 0, npages)
    maxlen = page * nslots
    lengths = jax.random.randint(k5, (B,), 1, maxlen + 1)
    out = ops.paged_attention(q, kp, vp, bt, lengths, interpret=True)
    want = ref.reference_paged_attention(q, kp, vp, bt, lengths)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_paged_attention_respects_block_table_permutation():
    """Same logical sequence under two different physical page placements
    must give identical outputs (pure paging invariance)."""
    k1, k2 = jax.random.split(KEY)
    B, H, KV, hd, page, nslots = 1, 4, 2, 32, 8, 3
    npages = 12
    q = jax.random.normal(k1, (B, H, hd))
    kv = jax.random.normal(k2, (nslots * page, KV, hd))
    lengths = jnp.asarray([20], jnp.int32)

    def place(order):
        kp = jnp.zeros((npages, KV, page, hd))
        vp = jnp.zeros((npages, KV, page, hd))
        for i, pg in enumerate(order):
            tile = kv[i * page:(i + 1) * page].swapaxes(0, 1)
            kp = kp.at[pg].set(tile)
            vp = vp.at[pg].set(tile * 0.5)
        return kp, vp, jnp.asarray([order], jnp.int32)

    o1 = ops.paged_attention(q, *place([0, 1, 2]), lengths, interpret=True)
    o2 = ops.paged_attention(q, *place([7, 3, 11]), lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
