#!/usr/bin/env python3
"""Records the small profiler trace that the benchmark's tests read.

    python3 bench/record_trace.py [--out bench/testdata/small.xplane.pb]

On the chip: a few steps of a small jitted training step through the
system's Pallas kernels (block-sparse attention forward and backward, the
pruned SwiGLU matmuls), inside the harness's own host spans
(``bench.window``, ``bench.loader``, ``bench.step``), with a host sleep
between steps so that the device has idle gaps to name.  The sizes and
number of calls are printed, so that the tests can check the reduction.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = 3
SLEEP_S = 0.02
T, D, FF, H, HD, BLOCK = 1024, 256, 512, 4, 64, 256


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(
        ROOT, "bench", "testdata", "small.xplane.pb"))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    from repro.models.layers import flash_attention, swiglu

    def loss(x, wi, wg, wo, q, k, v):
        y = swiglu(x, wi, wg, wo, jnp.ones((FF // 128,)), impl="pallas")
        nb = T // BLOCK
        bm = jnp.tril(jnp.ones((1, 1, nb, nb)))
        o = flash_attention(q, k, v, causal=True, block_mask=bm,
                            kv_block=BLOCK, impl="pallas")
        return jnp.sum(y * y) + jnp.sum(o * o)

    step = jax.jit(jax.grad(loss, argnums=tuple(range(7))))
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    shapes = [(T, D), (D, FF), (D, FF), (FF, D), (1, T, H, HD),
              (1, T, H, HD), (1, T, H, HD)]
    args_ = [jax.random.normal(k, s, jnp.float32) * 0.1
             for k, s in zip(keys, shapes)]
    jax.block_until_ready(step(*args_))
    tmp = tempfile.mkdtemp(prefix="bench-record-")
    jax.profiler.start_trace(tmp)
    win = jax.profiler.TraceAnnotation("bench.window")
    win.__enter__()
    for _ in range(STEPS):
        with jax.profiler.TraceAnnotation("bench.loader"):
            time.sleep(SLEEP_S)
        with jax.profiler.TraceAnnotation("bench.step"):
            jax.block_until_ready(step(*args_))
    win.__exit__(None, None, None)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    shutil.copy(path, args.out)
    shutil.rmtree(tmp, ignore_errors=True)
    d = jax.devices()[0]
    print(json.dumps({"steps": STEPS, "sleep_s": SLEEP_S, "T": T, "D": D,
                      "FF": FF, "H": H, "HD": HD, "block": BLOCK,
                      "kind": d.device_kind, "bytes": os.path.getsize(
                          args.out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
