"""Gradient parity for the Pallas backward kernels (interpret mode on CPU).

jax.grad through kernel_impl="pallas" must match the reference attention /
SwiGLU within atol 2e-2 across a density sweep, including GQA and a
non-multiple sequence length; fully-masked rows must produce zero (not NaN)
gradients.  Also checks the tile-work accounting helpers used by
benchmarks/bench_kernels.py."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.block_sparse_attention import (attention_tile_work,
                                                  block_sparse_attention)
from repro.kernels.pruned_matmul import (matmul_tile_work, pruned_matmul,
                                         pruned_matmul_ref, pruned_swiglu,
                                         pruned_swiglu_ref)
from repro.kernels.pruned_matmul.pruned_matmul import choose_tiles
from repro.models.layers import flash_attention, swiglu

NEG_INF = -1e30


def _dense_block_masked_ref(q, k, v, mask, bq):
    """Dense oracle with block-granular mask + token causal (fp32)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    kr = jnp.repeat(k, hq // hkv, axis=2)
    vr = jnp.repeat(v, hq // hkv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / np.sqrt(d)
    m = jnp.repeat(jnp.repeat(mask, bq, 2), bq, 3)[:, :, :s, :s] > 0
    m = m & (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :])
    sc = jnp.where(m, sc, NEG_INF)
    mx = jnp.max(sc, -1, keepdims=True)
    p = jnp.where(m, jnp.exp(sc - mx), 0.0)
    l = p.sum(-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bhqd", p, vr) / jnp.maximum(l, 1e-30)
    return jnp.where(l > 0, o, 0.0).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("density", [1.0, 0.5, 0.25])
@pytest.mark.parametrize("s,hq,hkv", [
    (128, 2, 2),
    (256, 4, 2),      # GQA
    (192, 4, 1),      # GQA + non-multiple of the 128 default block
])
def test_attention_grad_parity(density, s, hq, hkv):
    rng = np.random.RandomState(int(density * 100) + s)
    b, d, bq = 2, 32, 64
    q = jnp.asarray(rng.randn(b, s, hq, d) * 0.4, jnp.float32)
    k = jnp.asarray(rng.randn(b, s, hkv, d) * 0.4, jnp.float32)
    v = jnp.asarray(rng.randn(b, s, hkv, d) * 0.4, jnp.float32)
    nb = (s + bq - 1) // bq
    mask = jnp.asarray((rng.rand(b, hq, nb, nb) <= density).astype(np.int32))

    def loss_pallas(q, k, v):
        return jnp.sum(jnp.sin(block_sparse_attention(
            q, k, v, mask, causal=True, block_q=bq, block_k=bq,
            interpret=True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_dense_block_masked_ref(q, k, v, mask, bq)))

    gp = jax.grad(loss_pallas, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-2, err_msg=name)


def test_attention_fully_masked_rows_zero_grad():
    rng = np.random.RandomState(3)
    b, s, h, d, bq = 1, 128, 2, 32, 64
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    mask = jnp.zeros((b, h, s // bq, s // bq), jnp.int32)
    grads = jax.grad(
        lambda q, k, v: jnp.sum(block_sparse_attention(
            q, k, v, mask, causal=True, block_q=bq, block_k=bq,
            interpret=True)), (0, 1, 2))(q, k, v)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))
        assert bool(jnp.all(g == 0))


def test_layers_flash_attention_pallas_matches_scan_grads():
    """The model dispatch path: impl='pallas' grads == impl='scan' grads,
    dense causal (mask None) and hash-style per-batch block mask."""
    rng = np.random.RandomState(11)
    b, s, hq, hkv, d, blk = 2, 96, 4, 2, 16, 32
    q = jnp.asarray(rng.randn(b, s, hq, d) * 0.4, jnp.float32)
    k = jnp.asarray(rng.randn(b, s, hkv, d) * 0.4, jnp.float32)
    v = jnp.asarray(rng.randn(b, s, hkv, d) * 0.4, jnp.float32)
    nb = s // blk
    masks = [None,
             jnp.asarray((rng.rand(b, 1, nb, nb) > 0.3).astype(np.float32))]
    for bm in masks:
        def loss(impl, q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_mask=bm, kv_block=blk,
                impl=impl) ** 2)
        gs = jax.grad(lambda *a: loss("scan", *a), (0, 1, 2))(q, k, v)
        gp = jax.grad(lambda *a: loss("pallas", *a), (0, 1, 2))(q, k, v)
        for a, b_, name in zip(gp, gs, ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-2, err_msg=name)


# (queries, keys, causal): lengths off every candidate tile, so kv pads
UNMASKED_CASES = [
    pytest.param(600, 600, True, id="causal-600"),
    pytest.param(600, 600, False, id="noncausal-600"),
    pytest.param(333, 333, True, id="prefill-333"),
    pytest.param(200, 700, False, id="cross-200x700"),
]


@pytest.mark.parametrize("sq,sk,causal", UNMASKED_CASES)
def test_unmasked_pallas_attention_matches_reference(sq, sk, causal):
    """The unmasked Pallas path at the tiles ``choose_attention_tiles``
    gives the shape: output and the gradients of q, k and v against
    ``attention_reference`` (GQA, kv padded to the tile)."""
    from repro.models.layers import attention_reference
    rng = np.random.RandomState(sq + sk + causal)
    b, hq, hkv, d = 1, 2, 1, 64
    q = jnp.asarray(rng.randn(b, sq, hq, d) * 0.4, jnp.float32)
    k = jnp.asarray(rng.randn(b, sk, hkv, d) * 0.4, jnp.float32)
    v = jnp.asarray(rng.randn(b, sk, hkv, d) * 0.4, jnp.float32)
    g = jnp.asarray(rng.randn(b, sq, hq, d), jnp.float32)

    def pallas(q, k, v):
        return flash_attention(q, k, v, causal=causal, impl="pallas",
                               interpret=True)

    def reference(q, k, v):
        return attention_reference(q, k, v, causal=causal)
    # float32 both ways: rounding over at most a tile's 1024 terms
    np.testing.assert_allclose(np.asarray(pallas(q, k, v)),
                               np.asarray(reference(q, k, v)), atol=1e-6)
    gp = jax.grad(lambda *a: jnp.sum(pallas(*a) * g), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(reference(*a) * g), (0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("mask_axis", ["n", "k"])
@pytest.mark.parametrize("density", [1.0, 0.5, 0.25])
def test_pruned_matmul_grad_parity(mask_axis, density):
    rng = np.random.RandomState(int(density * 10))
    M, K, N = 100, 256, 384              # non-multiple M exercises padding
    x = jnp.asarray(rng.randn(M, K) * 0.2, jnp.float32)
    w = jnp.asarray(rng.randn(K, N) * 0.2, jnp.float32)
    nb = (N if mask_axis == "n" else K) // 128
    keep = max(1, int(round(nb * density)))
    mask = jnp.asarray([1] * keep + [0] * (nb - keep), jnp.int32)

    def loss_k(x, w):
        return jnp.sum(jnp.cos(pruned_matmul(
            x, w, mask, mask_axis=mask_axis, interpret=True)))

    def loss_r(x, w):
        return jnp.sum(jnp.cos(pruned_matmul_ref(
            x, w, mask, mask_axis=mask_axis)))

    gk = jax.grad(loss_k, (0, 1))(x, w)
    gr = jax.grad(loss_r, (0, 1))(x, w)
    for a, b_, name in zip(gk, gr, ("dx", "dw")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, err_msg=name)
        # pruned blocks contribute exactly zero weight gradient
    dw = np.asarray(gk[1])
    if mask_axis == "n":
        assert np.all(dw[:, keep * 128:] == 0)


# (density, tokens M, d, d_ff, mask block, seed): the first three at
# 128-wide blocks and their own seeds; then a d off the 128 grid, one
# unpruned block wider than the d_ff tile the kernel chooses, and a pruned
# mask with M off the token tile
SWIGLU_GRAD_CASES = [
    pytest.param(1.0, 64, 128, 512, 128, 11, id="1.0"),
    pytest.param(0.5, 64, 128, 512, 128, 6, id="0.5"),
    pytest.param(0.25, 64, 128, 512, 128, 3, id="0.25"),
    pytest.param(0.5, 64, 192, 512, 128, 262, id="d192"),
    pytest.param(1.0, 256, 1024, 4096, 4096, 1291, id="block-wider-than-tile"),
    pytest.param(0.5, 2100, 192, 512, 128, 2298, id="pruned-M2100"),
]


@pytest.mark.parametrize("density,M,d,ff,bf,seed", SWIGLU_GRAD_CASES)
def test_pruned_swiglu_grad_parity(density, M, d, ff, bf, seed):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(M, d) * 0.3, jnp.float32)
    wi = jnp.asarray(rng.randn(d, ff) * 0.05, jnp.float32)
    wg = jnp.asarray(rng.randn(d, ff) * 0.05, jnp.float32)
    wo = jnp.asarray(rng.randn(ff, d) * 0.05, jnp.float32)
    nb = ff // bf
    keep = max(1, int(round(nb * density)))
    mask = jnp.asarray([1] * keep + [0] * (nb - keep), jnp.int32)

    def loss_k(x, wi, wg, wo):
        return jnp.sum(pruned_swiglu(x, wi, wg, wo, mask,
                                     interpret=True) ** 2)

    def loss_r(x, wi, wg, wo):
        return jnp.sum(pruned_swiglu_ref(x, wi, wg, wo, mask, bf=bf) ** 2)

    gk = jax.grad(loss_k, (0, 1, 2, 3))(x, wi, wg, wo)
    gr = jax.grad(loss_r, (0, 1, 2, 3))(x, wi, wg, wo)
    for a, b_, name in zip(gk, gr, ("dx", "dwi", "dwg", "dwo")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, err_msg=name)


def test_layers_swiglu_pallas_matches_dense_grads():
    """Model dispatch: swiglu(impl='pallas') with the block-level dyn mask
    == the masked-XLA fallback, values and grads."""
    rng = np.random.RandomState(5)
    b, s, d, ff = 2, 16, 64, 256
    x = jnp.asarray(rng.randn(b, s, d) * 0.3, jnp.float32)
    wi = jnp.asarray(rng.randn(d, ff) * 0.05, jnp.float32)
    wg = jnp.asarray(rng.randn(d, ff) * 0.05, jnp.float32)
    wo = jnp.asarray(rng.randn(ff, d) * 0.05, jnp.float32)
    bmask = jnp.asarray([1.0, 0.0], jnp.float32)      # 2 blocks of 128

    def loss(impl, x, wi, wg, wo):
        return jnp.sum(swiglu(x, wi, wg, wo, bmask, impl=impl,
                              interpret=True) ** 2)

    ls = jax.value_and_grad(lambda *a: loss("scan", *a), (0, 1, 2, 3))
    lp = jax.value_and_grad(lambda *a: loss("pallas", *a), (0, 1, 2, 3))
    vs, gs = ls(x, wi, wg, wo)
    vp, gp = lp(x, wi, wg, wo)
    np.testing.assert_allclose(float(vp), float(vs), rtol=1e-5)
    for a, b_, name in zip(gp, gs, ("dx", "dwi", "dwg", "dwo")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, err_msg=name)


def test_tile_work_helpers_match_manual_count():
    rng = np.random.RandomState(0)
    nb, bq = 4, 64
    mask = (rng.rand(2, 3, nb, nb) > 0.5).astype(np.int32)
    work = attention_tile_work(mask, causal=True, block_q=bq, block_k=bq)
    tril = np.tril(np.ones((nb, nb), np.int32))
    manual = float((mask * tril).sum()) / (2 * 3)
    assert work["fwd_total"] == nb * (nb + 1) // 2
    assert abs(work["fwd_active"] - manual) < 1e-9
    assert work["bwd_active"] == 2 * work["fwd_active"]

    pm = matmul_tile_work(256, 512, 512, np.asarray([1, 0, 1, 0]),
                          mask_axis="n")
    # the kernels' grids at the tiles they choose (each a 256³ or
    # 256x256x512 tile; a tile over several mask blocks skips the dead
    # ones inside, so the live share of the work is the mask's)
    t = choose_tiles(256, 512, 512, "n", 128)
    assert (t.bm, t.bk, t.bn) == (256, 256, 512)
    assert pm["fwd_total"] == 1 * 2 * 1
    assert pm["fwd_active"] == pm["fwd_total"] * 0.5
    dx = choose_tiles(256, 512, 512, "k", 128)      # [M,N] @ [N,K]
    dw = choose_tiles(512, 256, 512, "n", 128)      # [K,M] @ [M,N]
    assert (dx.bm, dx.bk, dx.bn, dw.bm, dw.bk, dw.bn) == (
        256, 256, 512, 256, 256, 512)
    assert pm["bwd_total"] == 1 * 2 * 1 + 2 * 1 * 1
    assert pm["bwd_active"] / pm["bwd_total"] == 0.5


def test_rectangular_blocks_fully_masked_rows_zero():
    """block_q > block_k: a q-row whose only active tiles are entirely above
    the causal diagonal must emit 0 (regression: m_new == NEG_INF made
    p = exp(0) = 1, averaging v instead)."""
    rng = np.random.RandomState(0)
    b, s, h, d = 1, 128, 1, 32
    bq, bk = 128, 64
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    # only tile (0, 1): rows 0..63 cannot causally reach cols 64..127
    mask = jnp.zeros((b, h, 1, 2), jnp.int32).at[:, :, 0, 1].set(1)
    out = block_sparse_attention(q, k, v, mask, causal=True, block_q=bq,
                                 block_k=bk, interpret=True)
    out = np.asarray(out)
    assert np.abs(out[:, :64]).max() == 0.0, np.abs(out[:, :64]).max()
    assert np.all(np.isfinite(out))
    # and their gradients are zero, not NaN
    g = jax.grad(lambda q: jnp.sum(block_sparse_attention(
        q, k, v, mask, causal=True, block_q=bq, block_k=bk,
        interpret=True)))(q)
    assert bool(jnp.all(jnp.isfinite(g)))


@pytest.mark.parametrize("density", [1.0, 0.5])
def test_layers_gelu_mlp_pallas_matches_dense_grads(density):
    """Whisper enc/dec FFN dispatch: gelu_mlp(impl='pallas') == the masked
    dense path, values and grads."""
    from repro.models.layers import gelu_mlp
    rng = np.random.RandomState(int(density * 10))
    b, s, d, ff = 2, 8, 32, 256
    x = jnp.asarray(rng.randn(b, s, d) * 0.3, jnp.float32)
    w1 = jnp.asarray(rng.randn(d, ff) * 0.05, jnp.float32)
    b1 = jnp.asarray(rng.randn(ff) * 0.01, jnp.float32)
    w2 = jnp.asarray(rng.randn(ff, d) * 0.05, jnp.float32)
    b2 = jnp.asarray(rng.randn(d) * 0.01, jnp.float32)
    nb = ff // 128
    keep = max(1, int(round(nb * density)))
    bmask = jnp.asarray([1.0] * keep + [0.0] * (nb - keep), jnp.float32)

    def loss(impl, x, w1, b1, w2, b2):
        return jnp.sum(gelu_mlp(x, w1, b1, w2, b2, bmask, impl=impl,
                                interpret=True) ** 2)

    vs, gs = jax.value_and_grad(
        lambda *a: loss("scan", *a), (0, 1, 2, 3, 4))(x, w1, b1, w2, b2)
    vp, gp = jax.value_and_grad(
        lambda *a: loss("pallas", *a), (0, 1, 2, 3, 4))(x, w1, b1, w2, b2)
    np.testing.assert_allclose(float(vp), float(vs), rtol=1e-5)
    for a, b_, name in zip(gp, gs, ("dx", "dw1", "db1", "dw2", "db2")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, err_msg=name)


@pytest.mark.parametrize("density", [1.0, 0.5])
def test_noncausal_rectangular_grad_parity(density):
    """Cross-attention shape: sq != sk, causal=False, both non-multiples of
    the block — exercises the exact kv_len padded-column masking in fwd and
    bwd (the old wrapper could only pad safely for causal+square)."""
    rng = np.random.RandomState(int(density * 7))
    b, sq, sk, h, d, blk = 2, 48, 80, 2, 16, 32
    q = jnp.asarray(rng.randn(b, sq, h, d) * 0.4, jnp.float32)
    k = jnp.asarray(rng.randn(b, sk, h, d) * 0.4, jnp.float32)
    v = jnp.asarray(rng.randn(b, sk, h, d) * 0.4, jnp.float32)
    nqb, nkb = -(-sq // blk), -(-sk // blk)
    mask = jnp.asarray(
        (rng.rand(b, h, nqb, nkb) <= density).astype(np.int32))

    def ref(q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        m = jnp.repeat(jnp.repeat(mask, blk, 2), blk, 3)[:, :, :sq, :sk] > 0
        sc = jnp.where(m, sc, NEG_INF)
        mx = jnp.max(sc, -1, keepdims=True)
        p = jnp.where(m, jnp.exp(sc - mx), 0.0)
        l = p.sum(-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhd->bhqd", p, v) / jnp.maximum(l, 1e-30)
        return jnp.where(l > 0, o, 0.0).transpose(0, 2, 1, 3)

    def loss_pallas(q, k, v):
        return jnp.sum(jnp.sin(block_sparse_attention(
            q, k, v, mask, causal=False, block_q=blk, block_k=blk,
            interpret=True)))

    out = block_sparse_attention(q, k, v, mask, causal=False, block_q=blk,
                                 block_k=blk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               atol=2e-5)
    gp = jax.grad(loss_pallas, (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(ref(q, k, v))),
                  (0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-2, err_msg=name)
