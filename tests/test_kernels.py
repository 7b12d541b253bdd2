"""Pallas kernel validation: interpret-mode (CPU) vs the pure-jnp ref.py
oracles, swept over shapes / dtypes / sparsity levels."""
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.block_sparse_attention import (block_sparse_attention,
                                                  block_sparse_attention_ref,
                                                  choose_attention_tiles)
from repro.kernels.pruned_matmul import (pruned_matmul, pruned_matmul_ref,
                                         pruned_swiglu, pruned_swiglu_ref)
from repro.kernels.pruned_matmul.pruned_matmul import (FULL_AXIS,
                                                       MIN_SPLIT,
                                                       VMEM_BUDGET, Tiles,
                                                       choose_tiles,
                                                       padded_extent,
                                                       pruned_matmul_p,
                                                       restream, tile_cost)


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


# (sparsity, tokens M, d, d_ff, mask block, seed): the first three at
# 128-wide blocks, drawn as they always were (seed 9 draws every block
# dead); then a d off the 128 grid, one unpruned block wider than the d_ff
# tile the kernel chooses, and a pruned mask with M off the token tile,
# each with its first block held live; and a mask with every block dead
SWIGLU_CASES = [
    pytest.param(0.0, 64, 128, 512, 128, 0, id="0.0"),
    pytest.param(0.5, 64, 128, 512, 128, 5, id="0.5"),
    pytest.param(0.9, 64, 128, 512, 128, 9, id="0.9"),
    pytest.param(0.5, 64, 192, 512, 128, 261, id="d192"),
    pytest.param(0.0, 256, 1024, 4096, 4096, 1280, id="block-wider-than-tile"),
    pytest.param(0.5, 2100, 192, 512, 128, 2297, id="pruned-M2100"),
    pytest.param(1.0, 2100, 192, 512, 128, 2302, id="all-pruned-M2100"),
]


@pytest.mark.parametrize("sparsity,M,d,ff,bf,seed", SWIGLU_CASES)
def test_pruned_swiglu(sparsity, M, d, ff, bf, seed):
    if bf == ff:
        # the case's premise: the kernel cuts the single block in tiles
        assert choose_tiles(M, d, ff, "n", bf).bn < ff
    if M > FULL_AXIS and M % MIN_SPLIT:
        assert padded_extent(M) > M
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(M, d) * 0.3, jnp.float32)
    wi = jnp.asarray(rng.randn(d, ff) * 0.05, jnp.float32)
    wg = jnp.asarray(rng.randn(d, ff) * 0.05, jnp.float32)
    wo = jnp.asarray(rng.randn(ff, d) * 0.05, jnp.float32)
    nb = ff // bf
    mask = (rng.rand(nb) >= sparsity).astype(np.int32)
    if (M, d, ff) != (64, 128, 512) and sparsity < 1:
        mask[0] = 1
    mask = jnp.asarray(mask)
    out = pruned_swiglu(x, wi, wg, wo, mask, interpret=True)
    ref = pruned_swiglu_ref(x, wi, wg, wo, mask, bf=bf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    if not np.asarray(mask).any():
        assert np.all(np.asarray(out) == 0)


# smollm-360m's FFN at 8192 tokens: the three products of the forward and
# backward (M, K, N, mask axis), unpruned (one block of d_ff) and pruned
# at 128-wide blocks
_T, _D, _F = 8192, 960, 2560
FFN_PRODUCTS = {"up": (_T, _D, _F, "n"), "down": (_T, _F, _D, "k"),
                "up_dw": (_D, _T, _F, "n"), "down_dw": (_F, _T, _D, "m")}


@pytest.mark.parametrize("block", [_F, 128])
@pytest.mark.parametrize("product", sorted(FFN_PRODUCTS))
def test_choose_tiles_bounds_restream_and_vmem(product, block):
    """At the real widths each masked tile divides its mask block or is a
    whole number of them (skipped block by block inside the kernel), the
    blocks fit the 16 MiB of scoped VMEM, and no operand is read from HBM
    more than 8 times a call."""
    M, K, N, ax = FFN_PRODUCTS[product]
    t = choose_tiles(M, K, N, ax, block)
    b = {"m": t.bm, "k": t.bk, "n": t.bn}
    ext = {"m": M, "k": K, "n": N}
    assert block % b[ax] == 0 or b[ax] % block == 0
    assert all(ext[a] % b[a] == 0 for a in "mkn")
    cost = tile_cost(M, K, N, t, 4)
    assert cost.vmem <= VMEM_BUDGET < 16 << 20
    n = {a: ext[a] // b[a] for a in "mkn"}
    assert cost.restream == max(restream(t.order, n, "mk"),
                                restream(t.order, n, "kn"))
    assert cost.restream <= 8


@pytest.mark.parametrize("tile", [128, 256, 512])
@pytest.mark.parametrize("mask_axis,x_t,w_t", [
    ("n", False, False), ("k", False, False), ("k", False, True),
    ("n", False, True), ("n", True, False), ("m", True, False)])
def test_pruned_matmul_p_tilings(mask_axis, x_t, w_t, tile):
    """The kernel at explicit tiles, the masked one within a 256-wide mask
    block (128), equal to it, or over two blocks (512), each operand read
    as stored or transposed: equal to the masked dense product, with
    exact zeros in dead output blocks."""
    rng = np.random.RandomState(tile + len(mask_axis) + 2 * x_t + w_t)
    M, K, N, mb = 512, 512, 1024, 256
    ext = {"m": M, "k": K, "n": N}
    x = rng.randn(M, K).astype(np.float32)
    w = rng.randn(K, N).astype(np.float32)
    mask = np.asarray([1, 0, 1, 1, 0, 0, 1, 0])[:ext[mask_axis] // mb]
    b = {"m": 128, "k": 128, "n": 256}
    b[mask_axis] = tile
    out = pruned_matmul_p(
        jnp.asarray(x.T if x_t else x), jnp.asarray(w.T if w_t else w),
        jnp.asarray(mask), mask_axis=mask_axis, mask_block=mb, x_t=x_t,
        w_t=w_t, _tiles=Tiles(b["m"], b["k"], b["n"], "nmk"),
        interpret=True)
    keep = np.repeat(mask, mb).astype(np.float32)
    if mask_axis == "k":
        ref = (x * keep) @ w
    elif mask_axis == "n":
        ref = (x @ w) * keep
    else:
        ref = (x @ w) * keep[:, None]
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-3, rtol=1e-3)
    if mask_axis != "k":
        dead = keep == 0
        out = np.asarray(out)
        assert np.all((out[:, dead] if mask_axis == "n"
                       else out[dead]) == 0)


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
    kern = pruned_swiglu(x, wi, wg, wo, mask, interpret=True)
    model = swiglu(x, wi, wg, wo, jnp.repeat(mask.astype(jnp.float32), 64))
    np.testing.assert_allclose(np.asarray(kern), np.asarray(model),
                               atol=1e-4)


# (sq, sk, head_dim, causal) -> (block_q, block_k): the dense benchmark
# cell, gpt-paper's training length, whisper-large-v3's encoder window and
# its decoder's cross-attention onto it, a short unaligned prefill
TILE_CHOICES = [
    pytest.param((8192, 8192, 64, True), (1024, 512), id="smollm-dense8k"),
    pytest.param((2048, 2048, 32, True), (1024, 512), id="gpt-paper-2048"),
    pytest.param((1500, 1500, 64, False), (512, 512), id="whisper-enc-1500"),
    pytest.param((448, 1500, 64, False), (512, 512), id="cross-448x1500"),
    pytest.param((333, 333, 64, True), (512, 512), id="prefill-333"),
]


@pytest.mark.parametrize("shape,want", TILE_CHOICES)
def test_choose_attention_tiles_at_the_callers_shapes(shape, want):
    assert tuple(choose_attention_tiles(*shape)) == want


def test_attention_tile_choices_fit_the_scoped_vmem():
    """Every choice fits ``VMEM_BUDGET`` by ``attention_vmem``, which
    rejects the 1024 x 1024 tile that overflows the 16 MiB of scoped VMEM
    in the model's stacked layer scan (``tests/test_tpu_compile.py``
    compiles the choices there)."""
    from repro.kernels.block_sparse_attention.tiles import (VMEM_BUDGET,
                                                            attention_vmem)
    lengths = (1, 100, 333, 448, 1000, 1500, 2048, 4096, 8192, 32768)
    for sq, sk, hd, causal, itemsize in itertools.product(
            lengths, lengths, (32, 64, 128, 256), (True, False), (2, 4)):
        t = choose_attention_tiles(sq, sk, hd, causal, itemsize)
        assert attention_vmem(*t, hd, itemsize) <= VMEM_BUDGET, (
            sq, sk, hd, t)
    assert attention_vmem(1024, 1024, 64, 4) > VMEM_BUDGET


@pytest.mark.parametrize("block", [64, 512])
@pytest.mark.parametrize("masked", [True, False])
def test_pallas_attention_tiles_follow_the_mask_else_the_chooser(
        monkeypatch, masked, block):
    """A masked call runs the kernels at the mask's block, as it always
    did, and counts the mask's live tiles; an unmasked one runs at
    ``choose_attention_tiles``' tiles."""
    import repro.kernels.block_sparse_attention as bsa
    from repro.models.layers import attention_tiles, flash_attention
    seen = {}

    def spy(q, k, v, m, *, causal, block_q, block_k, interpret):
        seen.update(block_q=block_q, block_k=block_k, nb=m.shape[2:])
        return q
    monkeypatch.setattr(bsa, "block_sparse_attention", spy)
    b, s, h, hd = 2, 2048, 2, 64
    q = jnp.zeros((b, s, h, hd), jnp.float32)
    mask = None
    if masked:
        rng = np.random.RandomState(block)
        mask = jnp.asarray(rng.rand(b, 1, s // block, s // block) > 0.5,
                           jnp.float32)
    flash_attention(q, q, q, causal=True, block_mask=mask, kv_block=block,
                    impl="pallas")
    tiles = attention_tiles(mask, b, s, s, causal=True, head_dim=hd,
                            itemsize=4, kv_block=block, impl="pallas")
    if masked:
        assert (seen["block_q"], seen["block_k"]) == (block, block)
        assert seen["nb"] == (s // block, s // block)
        nb = s // block
        below = np.tril(np.ones((nb, nb)))
        assert float(tiles) == float(np.sum(np.asarray(mask) * below))
    else:
        want = choose_attention_tiles(s, s, hd, True, 4)
        assert (seen["block_q"], seen["block_k"]) == tuple(want)


@pytest.mark.parametrize("causal", [True, False])
def test_attention_tiles_count_the_kernels_live_tiles(causal):
    """``layers.attention_tiles`` counts, per head, the tiles the Pallas
    kernels compute: on a hash mask, the mask's own sum (the mask is
    already causal, and at equal blocks the kernels' causal gate keeps
    exactly the tiles on or below the diagonal); without a mask, at the
    tiles ``choose_attention_tiles`` gives the call, every causal tile of
    every sequence, or every tile when not causal; nothing on the scan
    path."""
    from repro.models.blocks import hash_block_mask
    from repro.models.layers import attention_tiles
    b, s, block, hd = 3, 512, 64, 32
    x = jnp.asarray(np.random.RandomState(1).randn(b, s, 32), jnp.float32)
    mask, density = hash_block_mask(x, nbuckets=4, block=block,
                                    causal=causal)
    got = attention_tiles(mask, b, s, s, causal=causal, head_dim=hd,
                          itemsize=4, kv_block=block, impl="pallas")
    nb = s // block
    assert float(got) == float(jnp.sum(mask))
    causal_tiles = nb * (nb + 1) / 2 if causal else nb * nb
    assert float(got) == pytest.approx(float(density) * causal_tiles * b)
    for seq in (s, 8192):
        bq, bk = choose_attention_tiles(seq, seq, hd, causal, 4)
        dense = attention_tiles(None, b, seq, seq, causal=causal,
                                head_dim=hd, itemsize=4, kv_block=512,
                                impl="pallas")
        nq, nk = seq // bq, seq // bk
        want = (sum(min(nk, (i * bq + bq - 1) // bk + 1) for i in range(nq))
                if causal else nq * nk)
        assert float(dense) == b * want
        if bq == bk:
            assert want == (nq * (nq + 1) // 2 if causal else nq * nq)
    assert float(attention_tiles(mask, b, s, s, causal=causal, head_dim=hd,
                                 itemsize=4, kv_block=block,
                                 impl="scan")) == 0.0
