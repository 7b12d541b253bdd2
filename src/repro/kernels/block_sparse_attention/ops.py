"""jit'd public wrapper: layout handling (GQA repeat, head flattening,
padding to block multiples) around the Pallas block-sparse attention kernel,
plus the custom-VJP that routes the backward through the Pallas flash
backward kernels (backward.py) — masked tiles skip work in both directions.
``interpret=True`` executes the kernel bodies on the CPU for validation;
callers pick it with ``repro.kernels.use_interpret()``."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.block_sparse_attention.backward import (
    block_sparse_attention_bwd_p)
from repro.kernels.block_sparse_attention.block_sparse_attention import (
    block_sparse_attention_p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _bsa_flat(q, k, v, block_mask, causal, block_q, block_k, kv_len,
              interpret):
    """Flat pre-padded attention (q/k/v: [BH, s, d], mask float [BH, nqb,
    nkb]).  Padding / GQA repeat happen OUTSIDE this boundary with
    differentiable jnp ops, so their transposes (slice / group-sum) come for
    free."""
    out, _ = block_sparse_attention_p(
        q, k, v, block_mask.astype(jnp.int32), causal=causal,
        block_q=block_q, block_k=block_k, kv_len=kv_len, interpret=interpret)
    return out


def _bsa_flat_fwd(q, k, v, block_mask, causal, block_q, block_k, kv_len,
                  interpret):
    out, lse = block_sparse_attention_p(
        q, k, v, block_mask.astype(jnp.int32), causal=causal,
        block_q=block_q, block_k=block_k, kv_len=kv_len, interpret=interpret)
    return out, (q, k, v, block_mask, out, lse)


def _bsa_flat_bwd(causal, block_q, block_k, kv_len, interpret, res, dout):
    q, k, v, block_mask, out, lse = res
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                  # [BH, sq, 1]
    dq, dk, dv = block_sparse_attention_bwd_p(
        q, k, v, block_mask.astype(jnp.int32), dout, lse, delta,
        causal=causal, block_q=block_q, block_k=block_k, kv_len=kv_len,
        interpret=interpret)
    return dq, dk, dv, jnp.zeros_like(block_mask)


_bsa_flat.defvjp(_bsa_flat_fwd, _bsa_flat_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def block_sparse_attention(q, k, v, block_mask, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: [b, sq, hq, d]; k, v: [b, sk, hkv, d];
    block_mask: [b, hq, ceil(sq/bq), ceil(sk/bk)] (0/1).

    Returns [b, sq, hq, d].  GQA handled by repeating kv heads; inputs are
    padded to block multiples.  Padded kv columns are masked exactly inside
    the kernels via the static ``kv_len`` (correct for non-causal and
    rectangular use too).  Differentiable: jax.grad routes through the
    Pallas flash backward with the same tile skipping as the forward."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)

    pq = (-sq) % block_q
    pk = (-sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nqb = (sq + pq) // block_q
    nkb = (sk + pk) // block_k
    assert block_mask.shape == (b, hq, nqb, nkb), (
        block_mask.shape, (b, hq, nqb, nkb))

    # flatten (b, h) and put heads on the leading axis: [BH, s, d]
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq + pq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hq, sk + pk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hq, sk + pk, d)
    mf = block_mask.reshape(b * hq, nqb, nkb).astype(jnp.float32)
    out = _bsa_flat(qf, kf, vf, mf, causal, block_q, block_k, sk, interpret)
    out = out.reshape(b, hq, sq + pq, d).transpose(0, 2, 1, 3)
    return out[:, :sq]


def attention_tile_work(block_mask, *, causal: bool = True,
                        block_q: int = 128, block_k: int = 128):
    """MXU tile-work accounting using the kernels' own gating predicates.

    block_mask: [..., nqb, nkb] (0/1).  Returns a dict with mean active and
    total (q-block × kv-block) tile counts per head for the forward and the
    backward (dq sweep + dk/dv sweep — each revisits the active tiles once).

    This is ACCOUNTING, not instrumentation: it recomputes the same
    (mask & causal-reachable) predicate the kernels gate on, so by
    construction bwd_ratio == fwd_ratio.  The *measured* signal that the
    backward really skips work is the fwd+bwd wall time reported next to
    these ratios by benchmarks/bench_kernels.py (falls with density), plus
    the gradient-parity tests that pin the predicates' correctness.
    """
    m = np.asarray(block_mask) > 0
    nqb, nkb = m.shape[-2], m.shape[-1]
    if causal:
        qi = np.arange(nqb)[:, None] * block_q + (block_q - 1)
        ki = np.arange(nkb)[None, :] * block_k
        reachable = ki <= qi
        m = m & reachable
        total = int(reachable.sum())
    else:
        total = nqb * nkb
    lead = int(np.prod(m.shape[:-2])) or 1
    active = float(m.sum()) / lead
    return {
        "fwd_active": active, "fwd_total": total,
        "bwd_active": 2.0 * active, "bwd_total": 2 * total,
    }
