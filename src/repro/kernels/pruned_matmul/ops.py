"""jit'd wrappers: padding + reshaping around the pruned matmul kernel, the
fused block-pruned SwiGLU built from the two mask positions, and the
custom-VJP that routes dx/dw through the same Pallas kernel with the mask
moved between slots (backward.py) — pruned blocks skip tile work in the
backward too.  Tiles are the kernel's own choice per product
(``choose_tiles``); the wrapper pads only what the chooser cuts."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pruned_matmul.backward import pruned_matmul_bwd_p
from repro.kernels.pruned_matmul.pruned_matmul import (padded_extent,
                                                       pruned_matmul_p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _pm_flat(x, w, block_mask, mask_axis, mask_block, interpret):
    """Flat pre-padded pruned matmul (x: [M, K], w: [K, N], mask float).
    Padding happens OUTSIDE this boundary with differentiable jnp ops."""
    return pruned_matmul_p(x, w, block_mask.astype(jnp.int32),
                           mask_axis=mask_axis, mask_block=mask_block,
                           interpret=interpret)


def _pm_flat_fwd(x, w, block_mask, mask_axis, mask_block, interpret):
    out = _pm_flat(x, w, block_mask, mask_axis, mask_block, interpret)
    return out, (x, w, block_mask)


def _pm_flat_bwd(mask_axis, mask_block, interpret, res, g):
    x, w, block_mask = res
    dx, dw = pruned_matmul_bwd_p(
        x, w, block_mask.astype(jnp.int32), g.astype(jnp.float32),
        mask_axis=mask_axis, mask_block=mask_block, interpret=interpret)
    return dx, dw, jnp.zeros_like(block_mask)


_pm_flat.defvjp(_pm_flat_fwd, _pm_flat_bwd)


@functools.partial(jax.jit, static_argnames=("mask_axis", "interpret"))
def pruned_matmul(x, w, block_mask, *, mask_axis: str = "n",
                  interpret: bool = False):
    """x: [..., K] @ w: [K, N] with a block mask over N or K.  The mask's
    block is the masked dim over its length (that dim must divide
    exactly); the other dims are zero-padded as far as the kernel's tiles
    need (``padded_extent``).  Differentiable: dx/dw run through the
    Pallas kernel with the mask in the transposed slot (same tile skipping
    as the forward)."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    masked = N if mask_axis == "n" else K
    nb = block_mask.shape[0]
    assert masked % nb == 0, ("masked dim must be a block multiple",
                              masked, nb)
    pm = padded_extent(M) - M
    pk = 0 if mask_axis == "k" else padded_extent(K) - K
    pn = 0 if mask_axis == "n" else padded_extent(N) - N
    if pm or pk:
        x2 = jnp.pad(x2, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    out = _pm_flat(x2, w, block_mask.astype(jnp.float32), mask_axis,
                   masked // nb, interpret)
    return out[:M, :N].reshape(*lead, N)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pruned_swiglu(x, wi, wg, wo, block_mask, *, interpret: bool = False):
    """Block-pruned SwiGLU MLP: up-projections mask output blocks ('n'),
    the down-projection skips the same blocks as reduction blocks ('k') —
    both matmuls genuinely skip the pruned tiles, forward and backward.
    The mask's block is d_ff over its length."""
    a = pruned_matmul(x, wg, block_mask, mask_axis="n", interpret=interpret)
    b = pruned_matmul(x, wi, block_mask, mask_axis="n", interpret=interpret)
    h = jax.nn.silu(a.astype(jnp.float32)) * b.astype(jnp.float32)
    return pruned_matmul(h.astype(x.dtype), wo, block_mask, mask_axis="k",
                         interpret=interpret)
