#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--fault-seeds 7,8,9] [--out FILE]

In one process, on the chip, at the cell's own sizes: for each seed the
system runs the cell's first three steps through its training entry, exactly
as a benchmark run's warm-up does, and the plain reference follows them; the
compared numbers are printed.  Then the same for

  control        the system with its own lower-precision path switched on
                 (bfloat16 parameters; the cell states float32)
  half_batch     half of each batch left out of the loss, the mean taken
                 over the rest
  mask_tile      (sparse attention) the system's hash mask with one tile
                 toggled in every layer, its density counted from it
  kernel_tile    (sparse attention) the same tile toggled in the mask the
                 attention kernel gets, the density left as the hash gave
                 it: a kernel that reads or skips a tile the mask does not
                 say
  no_exchange    (cells on several chips) the exchange between pipeline
                 stages left out: each stage keeps its own activations

A sound run reads below the limits; the control and each fault have to read
above one of them.  The benchmark's own runs run none of this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def half_batch(batch, lr):
    """Leave out the second half of the batch (the later micro-batches, or
    the later half of each sequence when there is one)."""
    import jax.numpy as jnp
    w = batch["label_mask"]
    if w.shape[0] >= 2:
        keep = (jnp.arange(w.shape[0]) < w.shape[0] // 2)[:, None, None]
    else:
        keep = (jnp.arange(w.shape[-1]) < w.shape[-1] // 2)[None, None, :]
    return dict(batch, label_mask=w * keep), lr


class no_exchange:
    """The pipeline's stage-to-stage ``ppermute`` returns its input."""

    def __enter__(self):
        import jax
        self._orig = jax.lax.ppermute
        jax.lax.ppermute = lambda x, axis_name, perm: x
        return self

    def __exit__(self, *exc):
        import jax
        jax.lax.ppermute = self._orig


class mask_tile:
    """The system's ``hash_block_mask`` with the tile of the last query
    block and the first key block toggled."""

    keep_density = False

    def __enter__(self):
        import jax.numpy as jnp
        import repro.models.blocks as blocks
        self._orig = orig = blocks.hash_block_mask
        keep = self.keep_density

        def toggled(x, **kw):
            mask, density = orig(x, **kw)
            mask = mask.at[..., -1, 0].set(1.0 - mask[..., -1, 0])
            if not keep:
                nb = mask.shape[-1]
                density = (jnp.sum(mask, axis=(1, 2, 3)).mean()
                           / (nb * (nb + 1) / 2))
            return mask, density
        blocks.hash_block_mask = toggled
        return self

    def __exit__(self, *exc):
        import repro.models.blocks as blocks
        blocks.hash_block_mask = self._orig


class kernel_tile(mask_tile):
    keep_density = True


CONTEXTS = {"no_exchange": no_exchange, "mask_tile": mask_tile,
            "kernel_tile": kernel_tile}


def one(cell, seed: int, kind: str) -> dict:
    import contextlib
    from bench.lib import compare, runner
    from bench.lib.drive import Drive
    batches = runner.batches_of(cell, seed)
    fault = half_batch if kind == "half_batch" else None
    dtype = "bfloat16" if kind == "control" else None
    ctx = CONTEXTS.get(kind, contextlib.nullcontext)()
    t0 = time.perf_counter()
    with ctx:
        out = Drive(cell, seed, batches, warmup=3, seconds=None,
                    fault=fault).run(
            runner.run_spec(cell, seed, param_dtype=dtype))
    t1 = time.perf_counter()
    try:
        g = runner.compared(cell, seed, out, batches)
    except ValueError as e:         # a fault may leave nothing to compare
        return {"kind": kind, "seed": seed, "error": repr(e)}
    t2 = time.perf_counter()
    rec = {"kind": kind, "seed": seed,
           **{k: g.get(k) for k in runner.READINGS + ("leaves",)},
           "system_losses": g["system_losses"],
           "reference_losses": g["reference_losses"],
           "density": g["density"], "needed_margin": g.get("needed_margin"),
           "rebalances": out.report["rebalances"],
           "final_lps": out.report["final_lps"],
           "system_s": t1 - t0, "reference_s": t2 - t1}
    rec["within_limits"] = compare.judge(g, cell.cell["limits"])[0]
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 1
    from bench.lib.spec import Cell
    cell = Cell(args.workload)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    plan = [(s, "sound") for s in ints(args.seeds)]
    plan += [(s, "control") for s in ints(args.control_seeds)]
    faults = ["half_batch"] + (["no_exchange"] if cell.chips > 1 else [])
    if cell.sparse is not None:
        faults += ["mask_tile", "kernel_tile"]
    plan += [(s, f) for f in faults for s in ints(args.fault_seeds)]
    for seed, kind in plan:
        line = json.dumps(one(cell, seed, kind))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as sink:
                sink.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
