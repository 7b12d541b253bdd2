"""Model FLOP/s utilization of the traced window: the dense architecture's
training FLOPs per token (``counts.model_flops_per_token``) times the
window's tokens over the window's length as the profiler recorded it, over
the chips' bf16 peak."""
from bench.lib import counts


def read(run):
    if run.peak is None or run.trace is None:
        return None
    f = counts.model_flops_per_token(run.config, run.cell.parallel["seq"])
    tokens = run.out.window_steps * run.cell.tokens_per_step
    return 100.0 * f * tokens / run.trace["window_s"] / (
        run.chips * run.peak["bf16_flops_per_s"])
