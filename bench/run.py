#!/usr/bin/env python3
"""The chip benchmark of the DynMo trainer.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Runs one cell of ``BENCHMARK.json`` on the accelerator this process finds,
through the trainer's own entry (``RunSpec -> Session.train``): set-up,
warm-up through the controller's first decision, a window of whole steps
that lasts ``--seconds``, then the plain float32 reference over the first
steps and the comparison that decides ``correct``.  With ``--trace 0`` the
metrics are the cell's end-to-end ones; with ``--trace 1`` the window runs
under the JAX profiler and the metrics are the per-layer ones.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each compared number beside its limit, which also end
stderr).  The run exits non-zero and prints no result when JAX finds no TPU
or fewer chips than the cell asks for, or when the system's ``src/`` is not
in the checkout.

JAX's persistent compilation cache lives in ``JAX_COMPILATION_CACHE_DIR``
when that is set, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: the system under test (src/repro) is not in "
              f"{ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib.spec import Cell
    cell = Cell(args.workload)
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench.lib import runner
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
