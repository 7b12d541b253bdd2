"""Compile-only checks of the Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel (or its gradient) at a real model
width for one chip of a ``v5e:2x2`` topology and compiles it with the TPU
compiler that ships with jaxlib.  This catches what interpret mode cannot
see — illegal block shapes, SMEM/VMEM overflows, unsupported lowerings —
without an attached chip.  The topology is described inside a fixture (the
TPU library may be loaded by one process at a time, and only the worker
that runs this file should load it).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

SEQ = 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the compiled HLO"
    return text


# (heads, kv heads, head_dim): gpt-paper (paper §5) and smollm-360m GQA
BSA_WIDTHS = {"gpt-paper": (32, 32, 32), "smollm-360m": (15, 5, 64)}


def _bsa_args(sh, arch, block=128):
    hq, hkv, hd = BSA_WIDTHS[arch]
    nb = SEQ // block
    return (_sds(sh, (1, SEQ, hq, hd)), _sds(sh, (1, SEQ, hkv, hd)),
            _sds(sh, (1, SEQ, hkv, hd)), _sds(sh, (1, hq, nb, nb)))


def _bsa(q, k, v, m):
    from repro.kernels.block_sparse_attention import block_sparse_attention
    return block_sparse_attention(q, k, v, m, causal=True, block_q=128,
                                  block_k=128, interpret=False)


@pytest.mark.parametrize("arch", sorted(BSA_WIDTHS))
def test_block_sparse_attention_forward_compiles(one_chip, arch):
    _compiled_text(_bsa, *_bsa_args(one_chip, arch))


@pytest.mark.parametrize("arch", sorted(BSA_WIDTHS))
def test_block_sparse_attention_grad_compiles(one_chip, arch):
    def loss(q, k, v, m):
        return jnp.sum(_bsa(q, k, v, m) ** 2)
    _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                   *_bsa_args(one_chip, arch))


# (tokens, d_model, heads, kv heads, head_dim, causal): smollm-360m at the
# benchmark's 8192, gpt-paper at 2048, whisper-large-v3's encoder window
UNMASKED_ATTENTION = {"smollm-360m-8k": (8192, 960, 15, 5, 64, True),
                      "gpt-paper": (SEQ, 1024, 32, 32, 32, True),
                      "whisper-encoder": (1500, 1280, 20, 20, 64, False)}


@pytest.mark.parametrize("arch", sorted(UNMASKED_ATTENTION))
def test_unmasked_attention_compiles_in_stacked_layers(one_chip, arch):
    """The unmasked Pallas path at the tiles ``choose_attention_tiles``
    gives the shape, forward and gradient, in two stacked layers of a
    rematerialised scan as the model runs them: where XLA fuses a kernel
    into its neighbours there, it holds it to the 16 MiB of scoped VMEM."""
    from repro.models.layers import flash_attention
    layers, (t, d, hq, hkv, hd, causal) = 2, UNMASKED_ATTENTION[arch]

    def attend(x, w):
        wq, wk, wv, wo = w
        q = (x @ wq).reshape(1, t, hq, hd)
        k = (x @ wk).reshape(1, t, hkv, hd)
        v = (x @ wv).reshape(1, t, hkv, hd)
        return flash_attention(q, k, v, causal=causal, impl="pallas",
                               interpret=False).reshape(1, t, hq * hd) @ wo

    def block(x, w):
        return x + attend(x, w), None

    def loss(x, *w):
        y, _ = jax.lax.scan(jax.checkpoint(block), x, w)
        return jnp.sum(y ** 2)
    args = (_sds(one_chip, (1, t, d)),
            _sds(one_chip, (layers, d, hq * hd)),
            _sds(one_chip, (layers, d, hkv * hd)),
            _sds(one_chip, (layers, d, hkv * hd)),
            _sds(one_chip, (layers, hq * hd, d)))
    _compiled_text(lambda x, *w: attend(x, [a[0] for a in w]), *args)
    _compiled_text(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *args)


def test_pruned_swiglu_grad_compiles(one_chip):
    """gpt-paper FFN (d_model 1024, d_ff 4096, 32 prune blocks of 128)."""
    from repro.kernels.pruned_matmul import pruned_swiglu
    d, f = 1024, 4096

    def loss(x, wi, wg, wo, mask):
        return jnp.sum(pruned_swiglu(x, wi, wg, wo, mask,
                                     interpret=False) ** 2)
    _compiled_text(jax.grad(loss, argnums=(0, 1, 2, 3)),
                   _sds(one_chip, (1, SEQ, d)), _sds(one_chip, (d, f)),
                   _sds(one_chip, (d, f)), _sds(one_chip, (f, d)),
                   _sds(one_chip, (f // 128,)))


def test_smollm_swiglu_grad_compiles_unpadded(one_chip):
    """smollm-360m FFN (d_model 960, d_ff 2560, a mask of 128-wide blocks
    as the model passes it) at 8192 tokens, two stacked layers in a
    rematerialised scan as the model runs them:
    every product's tiles fit the scoped VMEM also where XLA fuses the
    call into the update of a stacked gradient, and nothing pads the
    960-wide axis."""
    from repro.kernels.pruned_matmul import pruned_swiglu
    layers, t, d, f = 2, 8192, 960, 2560

    def block(x, w):
        return x + pruned_swiglu(x, *w, jnp.ones((f // 128,)),
                                 interpret=False), None

    def loss(x, wi, wg, wo):
        y, _ = jax.lax.scan(jax.checkpoint(block), x, (wi, wg, wo))
        return jnp.sum(y ** 2)
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2, 3)),
                          _sds(one_chip, (1, t, d)),
                          _sds(one_chip, (layers, d, f)),
                          _sds(one_chip, (layers, d, f)),
                          _sds(one_chip, (layers, f, d)))
    assert " pad(" not in text


@pytest.mark.parametrize("tokens", [777, 1000])
def test_smollm_swiglu_compiles_at_unaligned_tokens(one_chip, tokens):
    """A token count of 1024 or less, off the sublane grid or not, is one
    token tile (a serving prefill's mb × prompt length): the forward and
    the gradient, whose dw products contract over that tile read
    transposed, compile at smollm-360m's widths with a pruned mask."""
    from repro.kernels.pruned_matmul import pruned_swiglu
    d, f = 960, 2560
    mask = _sds(one_chip, (f // 128,))

    def fwd(x, wi, wg, wo, mask):
        return pruned_swiglu(x, wi, wg, wo, mask, interpret=False)

    def loss(x, wi, wg, wo, mask):
        return jnp.sum(fwd(x, wi, wg, wo, mask) ** 2)
    args = (_sds(one_chip, (1, tokens, d)), _sds(one_chip, (d, f)),
            _sds(one_chip, (d, f)), _sds(one_chip, (f, d)), mask)
    _compiled_text(fwd, *args)
    _compiled_text(jax.grad(loss, argnums=(0, 1, 2, 3)), *args)


def test_whisper_gelu_mlp_grad_compiles(one_chip):
    """whisper-large-v3's FFN (d_model 1280, d_ff 5120) over one 30 s
    encoder window (1500 frames, padded to the 1536-row tile grid), with
    a mask of 128-wide blocks."""
    from repro.models.layers import gelu_mlp
    t, d, f = 1500, 1280, 5120

    def loss(x, w1, b1, w2, b2, mask):
        return jnp.sum(gelu_mlp(x, w1, b1, w2, b2, mask, impl="pallas",
                                interpret=False) ** 2)
    _compiled_text(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                   _sds(one_chip, (1, t, d)), _sds(one_chip, (d, f)),
                   _sds(one_chip, (f,)), _sds(one_chip, (f, d)),
                   _sds(one_chip, (d,)), _sds(one_chip, (f // 128,)))


def test_grouped_matmul_grad_compiles(one_chip):
    """Two mixtral-8x7b experts (d_model 4096, d_ff 14336) in bfloat16."""
    from repro.kernels.grouped_matmul import grouped_matmul
    d, f, cap = 4096, 14336, 256

    def loss(x, w, counts):
        return jnp.sum(grouped_matmul(x, w, counts, interpret=False)
                       .astype(jnp.float32) ** 2)
    _compiled_text(jax.grad(loss, argnums=(0, 1)),
                   _sds(one_chip, (2, cap, d), jnp.bfloat16),
                   _sds(one_chip, (2, d, f), jnp.bfloat16),
                   _sds(one_chip, (2,), jnp.int32))


def test_paged_decode_attention_compiles(one_chip):
    """gpt-paper decode: 8 lanes, 16-token pages, a 2048-token line."""
    from repro.kernels.paged_attention import paged_attention
    lanes, page, hq, hd = 8, 16, 32, 32
    j = SEQ // page

    def decode(q, kp, vp, table, lens):
        return paged_attention(q, kp, vp, table, lens, interpret=False)
    _compiled_text(decode, _sds(one_chip, (lanes, 1, hq, hd)),
                   _sds(one_chip, (lanes * j + 1, page, hq, hd)),
                   _sds(one_chip, (lanes * j + 1, page, hq, hd)),
                   _sds(one_chip, (lanes, j), jnp.int32),
                   _sds(one_chip, (lanes,), jnp.int32))
