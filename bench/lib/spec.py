"""Finds a cell's files by the names in ``BENCHMARK.json``.

  BENCHMARK.json            the cell's configuration and traffic names
  bench/cells/<cell>.json   the job: layout, dynamism, the matmul
                            precision the system runs it at, warm-up steps,
                            and the limits of the comparison
  bench/configs/<config>    the model as it is run (the ``file`` entry)
  bench/mixes/<traffic>.json
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

# config-file key -> the system's ModelConfig field
PROGRAM_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "head_dim": "resolved_head_dim", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    def __init__(self, name: str, root: str = ROOT,
                 cell: Optional[dict] = None, config: Optional[dict] = None,
                 mix: Optional[dict] = None):
        self.name = name
        bm = _load(os.path.join(root, "BENCHMARK.json"))
        self.benchmark = bm
        if cell is None:
            wl = {w["name"]: w for w in bm["workloads"]}
            if name not in wl:
                raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                               f"known: {sorted(wl)}")
            self.workload = wl[name]
            conf = {c["name"]: c for c in bm["configs"]}[
                self.workload["config"]]
            config = _load(os.path.join(root, conf["file"]))
            cell = _load(os.path.join(BENCH, "cells", name + ".json"))
            mix = _load(os.path.join(BENCH, "mixes",
                                     self.workload["traffic"] + ".json"))
        else:
            self.workload = {"name": name, "chips": cell.get("chips", 1)}
        self.cell, self.config, self.mix = cell, config, mix
        self.chips = int(self.workload["chips"])

    def reference(self):
        """The configuration's plain reference module
        (``bench/configs/<reference>.py``), loaded once."""
        name = "bench_reference_" + self.config["reference"]
        mod = sys.modules.get(name)
        if mod is None:
            path = os.path.join(BENCH, "configs",
                                self.config["reference"] + ".py")
            s = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(s)
            sys.modules[name] = mod
            s.loader.exec_module(mod)
        return mod

    @property
    def parallel(self) -> dict:
        return self.cell["parallel"]

    @property
    def tokens_per_step(self) -> int:
        p = self.parallel
        return p["num_micro"] * p["mb_global"] * p["seq"]

    @property
    def one_sequence(self) -> bool:
        """A step holds one sequence, so the system's per-layer mask
        density is one mask's."""
        p = self.parallel
        return p["num_micro"] * p["mb_global"] == 1

    @property
    def sparse(self) -> Optional[dict]:
        d = self.cell["dynamics"]
        if d["kind"] != "sparse_attention":
            return None
        return {"block": d["sparse_block"], "nbuckets": d["sparse_nbuckets"],
                "precision": self.cell["matmul_precision"]}

    def e2e_metrics(self):
        return [m for m in self.benchmark["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def layer_metrics(self):
        return [m for m in self.benchmark["per_layer"]
                if self.name in m.get("workloads", [self.name])]

    def check_program_config(self, mc) -> None:
        """The system must run the configuration the file states."""
        for key, field in PROGRAM_FIELDS.items():
            want = self.config[key]
            got = getattr(mc, field)
            if (float(got) != float(want) if isinstance(want, (int, float))
                    and not isinstance(want, bool) else got != want):
                raise ValueError(
                    f"{self.config['registry']}: the system runs "
                    f"{field}={got!r}, the configuration file states "
                    f"{key}={want!r}")
