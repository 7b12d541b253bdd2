"""Roofline share of the block-pruned matmul kernel of the SwiGLU FFN: the
calls' least time on the chip (each call the larger of its FLOPs over the
bf16 peak and its bytes over HBM bandwidth, ``counts.pruned_matmul_call``)
over the kernel's device time.  All FFN blocks are live in these cells."""
from bench.lib import counts


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t, n = 0.0, 0
    for d in run.trace["devices"].values():
        sec, calls = d["kernels"].get("ffn_matmul", (0.0, 0))
        t += sec
        n += calls
    if n == 0 or t <= 0:
        return None
    p = run.cell.parallel
    flops, nbytes = counts.pruned_matmul_call(
        run.config, p["mb_global"] * p["seq"])
    least, _ = counts.roofline_seconds(flops, nbytes, run.peak)
    return 100.0 * n * least / t
