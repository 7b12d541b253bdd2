"""Pallas kernel validation: interpret-mode (CPU) vs the pure-jnp ref.py
oracles, swept over shapes / dtypes / sparsity levels."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.block_sparse_attention import (block_sparse_attention,
                                                  block_sparse_attention_ref)
from repro.kernels.pruned_matmul import (pruned_matmul, pruned_matmul_ref,
                                         pruned_swiglu, pruned_swiglu_ref)


def _bsa_ref_from_bhsd(q, k, v, mask, causal, bq, bk):
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    kr = jnp.repeat(k, hq // hkv, axis=2)
    vr = jnp.repeat(v, hq // hkv, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kf = kr.transpose(0, 2, 1, 3).reshape(b * hq, k.shape[1], d)
    vf = vr.transpose(0, 2, 1, 3).reshape(b * hq, v.shape[1], d)
    mf = mask.reshape(b * hq, mask.shape[2], mask.shape[3])
    ref = block_sparse_attention_ref(qf, kf, vf, mf, causal=causal,
                                     block_q=bq, block_k=bk)
    return ref.reshape(b, hq, s, d).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("s,hq,hkv,d,bq", [
    (128, 2, 2, 32, 64),
    (256, 4, 2, 64, 64),
    (192, 2, 1, 32, 64),     # non-power-of-two seq
])
@pytest.mark.parametrize("density", [1.0, 0.5, 0.15])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_sparse_attention_sweep(s, hq, hkv, d, bq, density, dtype):
    rng = np.random.RandomState(hash((s, hq, density == 1.0)) % 2 ** 31)
    b = 2
    q = jnp.asarray(rng.randn(b, s, hq, d) * 0.4, dtype)
    k = jnp.asarray(rng.randn(b, s, hkv, d) * 0.4, dtype)
    v = jnp.asarray(rng.randn(b, s, hkv, d) * 0.4, dtype)
    nqb = (s + bq - 1) // bq
    mask = (rng.rand(b, hq, nqb, nqb) <= density).astype(np.int32)
    out = block_sparse_attention(q, k, v, jnp.asarray(mask), causal=True,
                                 block_q=bq, block_k=bq, interpret=True)
    # oracle works on the padded shapes
    pq = (-s) % bq
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pq), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pq), (0, 0), (0, 0)))
    ref = _bsa_ref_from_bhsd(qp, kp, vp, jnp.asarray(mask), True, bq, bq)
    ref = ref[:, :s]
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_bsa_dense_mask_equals_flash():
    """Full mask == ordinary causal attention (cross-check vs the model's
    flash oracle)."""
    from repro.models.layers import flash_attention
    rng = np.random.RandomState(0)
    b, s, h, d, bq = 1, 128, 2, 32, 64
    q = jnp.asarray(rng.randn(b, s, h, d) * 0.4, jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d) * 0.4, jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d) * 0.4, jnp.float32)
    mask = jnp.ones((b, h, s // bq, s // bq), jnp.int32)
    out = block_sparse_attention(q, k, v, mask, causal=True, block_q=bq,
                                 block_k=bq, interpret=True)
    ref = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.parametrize("M,K,N", [(64, 256, 384), (100, 128, 128),
                                   (257, 384, 256)])
@pytest.mark.parametrize("mask_axis", ["n", "k"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pruned_matmul_sweep(M, K, N, mask_axis, dtype):
    rng = np.random.RandomState(M + K + N)
    x = jnp.asarray(rng.randn(M, K) * 0.2, dtype)
    w = jnp.asarray(rng.randn(K, N) * 0.2, dtype)
    nb = (N if mask_axis == "n" else K) // 128
    mask = jnp.asarray((rng.rand(nb) > 0.4).astype(np.int32))
    out = pruned_matmul(x, w, mask, mask_axis=mask_axis, interpret=True)
    ref = pruned_matmul_ref(x, w, mask, mask_axis=mask_axis)
    atol = 1e-3 if dtype == jnp.float32 else 0.25
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol,
                               rtol=1e-2)


@pytest.mark.parametrize("sparsity", [0.0, 0.5, 0.9])
def test_pruned_swiglu(sparsity):
    rng = np.random.RandomState(int(sparsity * 10))
    M, d, ff = 64, 128, 512
    x = jnp.asarray(rng.randn(M, d) * 0.3, jnp.float32)
    wi = jnp.asarray(rng.randn(d, ff) * 0.05, jnp.float32)
    wg = jnp.asarray(rng.randn(d, ff) * 0.05, jnp.float32)
    wo = jnp.asarray(rng.randn(ff, d) * 0.05, jnp.float32)
    nb = ff // 128
    mask = jnp.asarray((rng.rand(nb) >= sparsity).astype(np.int32))
    out = pruned_swiglu(x, wi, wg, wo, mask, interpret=True)
    ref = pruned_swiglu_ref(x, wi, wg, wo, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_pruned_matmul_matches_model_semantics():
    """Kernel semantics == the masked-XLA fallback used by blocks.swiglu."""
    from repro.models.layers import swiglu
    rng = np.random.RandomState(7)
    M, d, ff = 32, 64, 256
    x = jnp.asarray(rng.randn(M, d) * 0.3, jnp.float32)
    wi = jnp.asarray(rng.randn(d, ff) * 0.05, jnp.float32)
    wg = jnp.asarray(rng.randn(d, ff) * 0.05, jnp.float32)
    wo = jnp.asarray(rng.randn(ff, d) * 0.05, jnp.float32)
    mask = jnp.asarray([1, 0, 1, 1], jnp.int32)     # 4 blocks of 64 = ff 256
    kern = pruned_swiglu(x, wi, wg, wo, mask, bf=64, interpret=True)
    model = swiglu(x, wi, wg, wo, jnp.repeat(mask.astype(jnp.float32), 64))
    np.testing.assert_allclose(np.asarray(kern), np.asarray(model),
                               atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_attention_tiles_count_the_kernels_live_tiles(causal):
    """``layers.attention_tiles`` counts, per head, the tiles the Pallas
    kernels compute: on a hash mask, the mask's own sum (the mask is
    already causal, and at equal blocks the kernels' causal gate keeps
    exactly the tiles on or below the diagonal); without a mask, at the
    kernels' 128-token blocks, every causal tile of every sequence, or
    every tile when not causal; nothing on the scan path."""
    from repro.models.blocks import hash_block_mask
    from repro.models.layers import attention_tiles
    b, s, block = 3, 512, 64
    x = jnp.asarray(np.random.RandomState(1).randn(b, s, 32), jnp.float32)
    mask, density = hash_block_mask(x, nbuckets=4, block=block,
                                    causal=causal)
    got = attention_tiles(mask, b, s, s, causal=causal, kv_block=block,
                          impl="pallas")
    nb = s // block
    assert float(got) == float(jnp.sum(mask))
    causal_tiles = nb * (nb + 1) / 2 if causal else nb * nb
    assert float(got) == pytest.approx(float(density) * causal_tiles * b)
    dense = attention_tiles(None, b, s, s, causal=causal, kv_block=512,
                            impl="pallas")
    n128 = s // 128
    assert float(dense) == b * (n128 * (n128 + 1) // 2 if causal
                                else n128 * n128)
    assert float(attention_tiles(mask, b, s, s, causal=causal,
                                 kv_block=block, impl="scan")) == 0.0
