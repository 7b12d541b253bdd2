"""Operations and bytes the work needs, from shapes alone.

``model_flops_per_token`` is the architecture's training work per token:
6 x the matmul weights a token passes (the LM head included, the embedding
lookup excluded), plus 6 x L x s x (n_q x hd) for causal attention
(QK^T and PV, 2 FLOPs a multiply-add, half the pairs causal, forward and the
two backward products).  It is the dense architecture's work whatever the
implementation skips or recomputes.

``pruned_matmul_call`` is one call of the block-pruned matmul kernel in a
SwiGLU FFN.  Every call of a step, forward or backward, multiplies over the
three sizes (tokens, d, d_ff) in some order, so each needs 2 T d ff FLOPs
and reads and writes T d + d ff + T ff elements at least.
"""
from __future__ import annotations


def _d(c: dict):
    return (c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["vocab_size"], c["num_hidden_layers"])


def matmul_weights_per_layer(c: dict) -> int:
    d, ff, nq, nkv, hd, _, _ = _d(c)
    return 2 * d * nq * hd + 2 * d * nkv * hd + 3 * d * ff


def model_flops_per_token(c: dict, seq: int) -> float:
    d, _, nq, _, hd, V, L = _d(c)
    n_matmul = L * matmul_weights_per_layer(c) + d * V
    return 6.0 * n_matmul + 6.0 * L * seq * nq * hd


def pruned_matmul_call(c: dict, tokens: int, itemsize: int = 4):
    """(FLOPs, bytes) of one FFN matmul call over ``tokens`` rows."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    flops = 2.0 * tokens * d * ff
    nbytes = float(itemsize) * (tokens * d + d * ff + tokens * ff)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """(least seconds, bound) of work on a chip: the larger of operations
    over peak FLOP/s and bytes over peak bytes/s."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
