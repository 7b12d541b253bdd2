"""Tests of the benchmark's reading of the system's own spans and tile
count (``bench/lib/spans.py``, ``bench/lib/tiles.py``, ``bench/attribute.py``),
on the CPU."""
from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SRC = os.path.join(REPO, "src")
for p in (REPO, SRC):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import spans, tiles  # noqa: E402

# one loop iteration of the system, in ns: the loader's wait, the step
# (placement, dispatch, the wait for the device), the controller
SPANS = [("train.iter", 0, 100), ("train.data", 0, 30),
         ("train.step", 30, 80), ("engine.place", 30, 40),
         ("engine.dispatch", 40, 50), ("train.wait", 50, 80),
         ("controller.decide", 80, 95)]


def test_idle_is_split_by_overlap_over_the_innermost_span():
    idle = [(20, 35), (45, 60), (95, 110)]
    got = spans.idle_by_span(idle, SPANS)
    # a gap across two spans is split between them by overlap (not put
    # down to the span at its middle); the innermost span wins over
    # train.step and train.iter; what no span covers is "none"
    want = {"train.data": 10e-9, "engine.place": 5e-9,
            "engine.dispatch": 5e-9, "train.wait": 10e-9,
            "train.iter": 5e-9, "none": 10e-9}
    assert got.keys() == want.keys()
    for k, v in want.items():
        assert got[k] == pytest.approx(v), k
    assert sum(got.values()) == pytest.approx(sum(b - a for a, b in idle)
                                              * 1e-9)


def test_the_five_idle_shares_sum_to_the_idle_share():
    idle = [(20, 35), (45, 60), (85, 110)]
    window_s = 200e-9
    red = {"window_s": window_s, "spans": len(SPANS),
           "devices": {0: {"idle_by_span": spans.idle_by_span(idle, SPANS)}}}
    got = spans.shares(red)
    assert set(got) == {"idle_data_share", "idle_engine_share",
                        "idle_controller_share", "idle_loop_share",
                        "idle_unattributed_share"}
    assert got["idle_data_share"] == pytest.approx(100 * 10 / 200)
    assert got["idle_engine_share"] == pytest.approx(100 * 10 / 200)
    assert got["idle_controller_share"] == pytest.approx(100 * 10 / 200)
    assert got["idle_loop_share"] == pytest.approx(100 * 15 / 200)
    assert got["idle_unattributed_share"] == pytest.approx(100 * 10 / 200)
    idle_share = 100 * sum(b - a for a, b in idle) * 1e-9 / window_s
    assert sum(got.values()) == pytest.approx(idle_share)
    # a trace of a program that marks no spans reads nothing
    assert spans.shares(dict(red, spans=0)) == {}


def test_tile_counts_match_hand_derived():
    b, hd = 512, 64
    assert tiles.tile("forward", b, hd) == (4.0 * b * b * hd,
                                            4.0 * 2 * b * hd)
    assert tiles.tile("dq", b, hd) == (6.0 * b * b * hd, 4.0 * 2 * b * hd)
    assert tiles.tile("dkv", b, hd) == (8.0 * b * b * hd,
                                        4.0 * 2 * b * hd + 4 * 2 * b)
    peak = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    c = {"num_attention_heads": 15, "head_dim": 64}
    # 512-token tiles at hd 64 are compute-bound: 22 b^2 hd FLOPs a tile
    # and head (the forward twice, dq, dk/dv)
    assert tiles.least_seconds(c, 10, 512, peak) == pytest.approx(
        10 * 15 * 22 * 512 * 512 * 64 / 1.97e14)
    # 128-token tiles are bound by their bytes in every kernel: the
    # forward (twice) and dq read a key and a value block, dk/dv a query
    # and an output-gradient block and the two row columns
    kv = 4.0 * 2 * 128 * 64
    assert tiles.least_seconds(c, 10, 128, peak) == pytest.approx(
        10 * 15 * (2 * kv + kv + kv + 4 * 2 * 128) / 8.19e11)


def test_attribute_reads_the_windows_tiles_from_the_system():
    """A tiny traced dense run on the CPU: the window's tiles read from the
    engine are the window's steps x layers x the 3 causal tiles of 128
    tokens that each 256-token sequence costs."""
    sys.path.insert(0, os.path.join(REPO, "tests", "bench"))
    from test_bench import tiny_cell
    from bench.attribute import attribute
    cell = tiny_cell()
    cell.cell["dynamics"] = {"kind": "none"}
    cell.cell["limits"] = {}
    result, out = attribute(cell, 20251017, 0.3, require_tpu=False,
                            log=lambda *a, **k: None)
    assert result["attempted"] >= 1
    assert out["block"] == 128
    assert out["window_tiles"] == result["attempted"] * 4 * 3
