"""Tests of the chip benchmark's own code (``bench/``), on the CPU.

They check the yardstick rather than the system: the peak table, the
operation counts against hand-derived ones, the trace reduction on a trace
recorded on the chip, the traffic generator against the hash mask, the
plain reference against the trainer at a tiny size, and that a run whose
timed path is broken comes out not correct.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SRC = os.path.join(REPO, "src")
for p in (REPO, SRC):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import compare, counts, peaks, trace, traffic  # noqa: E402
from bench.lib.spec import Cell  # noqa: E402

TRACE = os.path.join(REPO, "bench", "testdata", "small.xplane.pb")
MIX = json.load(open(os.path.join(REPO, "bench", "mixes",
                                  "docs-topics.json")))

# A tiny cell of the system's dense block: 4 layers of width 64, 2 micro-
# batches of 256 tokens, hash sparse attention over blocks of 32.
TINY_CONFIG = {
    "registry": "smollm-360m", "reference": "dense_reference",
    "registry_reduction": {"layers": 4, "d_model": 64, "num_heads": 4,
                           "num_kv_heads": 2, "d_ff": 256,
                           "vocab_size": 512},
    "hidden_size": 64, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False}
# float32 on the CPU against float32 at `highest`: the two agree to
# rounding (sound tiny runs read 7e-8 / 8e-7 / 4e-7, and no hash bit read
# the other way); a step of one sequence (one stage) also compares the mask
TINY_LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-4, "update_gap": 1e-4}
TINY_MASK_LIMIT = {"mask_margin": 1e-4}


def tiny_cell(stages: int = 1) -> Cell:
    limits = dict(TINY_LIMITS, **(TINY_MASK_LIMIT if stages == 1 else {}))
    cell = {
        "parallel": {"stages": stages, "num_micro": stages, "mb_global": 1,
                     "seq": 256, "slot_slack": 0 if stages == 1 else 1,
                     "remat": "block", "param_dtype": "float32",
                     "kernel_impl": "pallas"},
        "dynamics": {"kind": "sparse_attention", "sparse_block": 32,
                     "sparse_nbuckets": 8},
        "controller": {"balancer": "diffusion", "rebalance_every": 2},
        "matmul_precision": "default",
        "warmup_steps": 4, "limits": limits, "chips": stages}
    mix = dict(MIX, doc_len={"median": 48, "sigma": 1.0, "min": 8})
    return Cell("tiny", cell=cell, config=dict(TINY_CONFIG), mix=mix)


# ---------------------------------------------------------------------------
# peaks and counts
# ---------------------------------------------------------------------------
def test_peak_table_is_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 1.97e14
    assert p["hbm_bytes_per_s"] == 8.19e11
    assert "cloud.google" in p["source"].lower() or "Google" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


HAND = {"hidden_size": 4, "intermediate_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 2, "vocab_size": 10,
        "num_hidden_layers": 1}


@pytest.mark.parametrize("what,got,want", [
    # wq 4x4 + wk 4x2 + wv 4x2 + wo 4x4 + 3 x (4x8) = 144
    ("layer weights", lambda: counts.matmul_weights_per_layer(HAND), 144),
    # 6 x (144 + head 4x10) + 6 x 1 layer x 4 tokens x (2 x 2)
    ("flops per token", lambda: counts.model_flops_per_token(HAND, 4),
     6 * 184 + 96),
    # 2 x 16 x 4 x 8 FLOPs; 4 bytes x (16x4 + 4x8 + 16x8)
    ("ffn call", lambda: counts.pruned_matmul_call(HAND, 16),
     (1024.0, 896.0)),
    ("roofline compute-bound", lambda: counts.roofline_seconds(
        197.0, 1.0, {"bf16_flops_per_s": 197.0, "hbm_bytes_per_s": 819.0}),
     (1.0, "compute")),
    ("roofline memory-bound", lambda: counts.roofline_seconds(
        1.0, 819.0, {"bf16_flops_per_s": 197.0, "hbm_bytes_per_s": 819.0}),
     (1.0, "memory")),
])
def test_counts_match_hand_derived(what, got, want):
    assert got() == want, what


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def test_union_and_leaves():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    evs = [("loop", 0, 10), ("a", 1, 4), ("b", 5, 9), ("c", 12, 13)]
    assert [e[0] for e in trace._leaves(evs)] == ["a", "b", "c"]
    assert trace.op_name("%pruned_matmul.9 = f32[8,8]{1,0} custom-call("
                         "s32[4]{0} %b, f32[8,8]{1,0} %x)") == "pruned_matmul.9"
    assert trace.kernel_of("pruned_matmul.12") == "ffn_matmul"
    assert trace.kernel_of("block_sparse_attention.52") == "attention"
    assert trace.kernel_of(
        "transpose_jvp_jit_block_sparse_attention___.3") == "attention"
    # a fusion that reads a kernel's output is not the kernel
    assert trace.kernel_of(trace.op_name(
        "%fusion.5 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %pruned_matmul.10)"
    )) is None


def test_trace_reduction_on_recorded_chip_trace():
    """bench/record_trace.py on a TPU v5 lite: 3 steps of a SwiGLU FFN and
    a block-sparse attention, forward and backward, 20 ms of host sleep
    (``bench.loader``) before each."""
    red = trace.reduce(TRACE)
    assert list(red["devices"]) == [0]
    d = red["devices"][0]
    w = red["window_s"]
    assert 3 * 0.02 < w < 5.0
    assert 0 < d["busy_s"] < w
    k = d["kernels"]
    # per step: 3 forward matmuls, 2 backward each (dx, dw); attention
    # forward, then the dq and the dk/dv sweeps
    assert k["ffn_matmul"][1] == 3 * 9
    assert k["attention"][1] == 3 * 3
    gaps = dict()
    for label, sec in d["gaps"]:
        gaps[label] = gaps.get(label, 0.0) + sec
    assert gaps.get("loader", 0.0) >= 3 * 0.02 * 0.9
    idle = sum(gaps.values())
    assert abs((d["busy_s"] + idle) - w) < 1e-6 * max(1.0, w) + 1e-6
    bd = trace.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["idle_gaps"][0][0].startswith("loader")


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def test_traffic_is_a_function_of_seed_and_step():
    a = traffic.make(MIX, 7, 1000, 2, 1, 512)
    b = traffic.make(MIX, 7, 1000, 2, 1, 512)
    c = traffic.make(MIX, 2 ** 31 + 11, 1000, 2, 1, 512)
    x, y, z = a(3), b(3), c(3)
    assert x["tokens"].shape == (2, 1, 512) and x["tokens"].dtype == np.int32
    np.testing.assert_array_equal(x["tokens"], y["tokens"])
    np.testing.assert_array_equal(x["labels"][..., :-1], x["tokens"][..., 1:])
    assert not np.array_equal(x["tokens"], z["tokens"])
    assert not np.array_equal(a(3)["tokens"], a(4)["tokens"])
    # document sizes are the mix's, whatever the seed
    np.testing.assert_array_equal(a.documents(3, 1)[0], c.documents(3, 1)[0])


def test_zipf_bigram_reads_like_the_system_stream():
    """The copy of the system's synthetic stream is a function of (seed,
    step) and has its statistics: the share of the commonest token and of
    tokens that are their predecessor's successor."""
    from repro.data.synthetic import zipf_token_stream
    mix = json.load(open(os.path.join(REPO, "bench", "mixes",
                                      "zipf-bigram.json")))
    n, seed = 1 << 16, 2 ** 31 + 11
    a = traffic.make(mix, seed, 1000, 1, 1, n)
    x = a(3)
    assert x["tokens"].shape == (1, 1, n) and x["tokens"].dtype == np.int32
    np.testing.assert_array_equal(
        x["tokens"], traffic.make(mix, seed, 1000, 1, 1, n)(3)["tokens"])
    assert not np.array_equal(x["tokens"], a(4)["tokens"])
    np.testing.assert_array_equal(x["labels"][..., :-1], x["tokens"][..., 1:])
    t = a.row(3, 0)
    p = next(zipf_token_stream(1000, seed=3, block=n))
    p_succ = np.random.RandomState(3).permutation(1000)
    for got, want in ((np.mean(t == 0), np.mean(p == 0)),
                      (np.mean(t[1:] == a.succ[t[:-1]]),
                       np.mean(p[1:] == p_succ[p[:-1]]))):
        assert abs(got - want) < 0.01, (got, want)


def test_docs_topics_blocks_of_one_document_hash_alike():
    """At smollm-360m's widths and the cell's 8192 x 512 blocks, under the
    system's hash mask at the first layer's input, two blocks inside one
    document share a bucket far more often than two blocks that share no
    document (pairs of neighbouring blocks, always live, left out)."""
    import jax
    import jax.numpy as jnp
    from repro.models.blocks import hash_block_mask
    from repro.models.layers import rms_norm
    d, V, S, B = 960, 49152, 8192, 512
    emb = jax.random.normal(jax.random.PRNGKey(0), (V, d)) * 0.02
    gen = traffic.make(MIX, 5, V, 1, 1, S)
    same, diff = [], []
    nb = S // B
    for step in range(32):
        tok = gen(step)["tokens"][0]
        x = rms_norm(emb[tok], jnp.ones((d,)))
        mask, _ = hash_block_mask(x, nbuckets=8, block=B)
        mask = np.asarray(mask)[0, 0]
        lens, _ = gen.documents(step, 0)
        doc = np.repeat(np.arange(len(lens)), lens)[:S]
        docs = [set(doc[i * B:(i + 1) * B]) for i in range(nb)]
        for i in range(nb):
            for j in range(i - 1):
                if len(docs[i]) == 1 and docs[i] == docs[j]:
                    same.append(mask[i, j])
                elif not docs[i] & docs[j]:
                    diff.append(mask[i, j])
    assert len(same) >= 10 and len(diff) >= 100
    p_same, p_diff = float(np.mean(same)), float(np.mean(diff))
    print("same-document", p_same, "different documents", p_diff)
    assert p_same > 0.4 and p_diff < 0.25 and p_same > 3 * p_diff, (
        p_same, p_diff)


# ---------------------------------------------------------------------------
# the comparison and the reference
# ---------------------------------------------------------------------------
def test_gaps_leave_out_quiet_leaves_and_take_the_worst():
    ref = {"losses": [2.0, 1.0, 1.0],
           "grad_norms": {"a": 1.0, "b": 2.0, "c": 3.0, "q": 1e-6},
           "delta_norms": {"a": 1.0, "b": 1.0, "c": 1.0, "q": 1.0}}
    g = compare.gaps([2.2, 1.0, 1.0, 9.0],
                     {"a": 1.0, "b": 2.5, "c": 3.0, "q": 5.0},
                     {"a": 1.0, "b": 1.0, "c": 0.0, "q": 9.0}, ref)
    assert g["loss_gap"] == pytest.approx(0.1)
    assert g["grad_gap"] == pytest.approx(0.25) and g["grad_leaf"] == "b"
    assert g["update_gap"] == pytest.approx(1.0) and g["update_leaf"] == "c"
    assert g["quiet_leaves"] == ["q"]
    ok, checks = compare.judge(g, {"loss_gap": 0.2, "grad_gap": 0.3})
    assert ok and checks["grad_gap"] == {"value": g["grad_gap"],
                                         "limit": 0.3}
    assert not compare.judge(g, {"update_gap": 0.5})[0]


def _tiny_run(cell, fault=None, seconds=0.3):
    from bench.lib import runner
    return runner.run(cell, 20251017, seconds, False, require_tpu=False,
                      fault=fault, log=lambda *a, **k: None)


def test_reference_matches_the_trainer_and_sees_one_tile():
    """The reference follows Session.train's first three steps to rounding,
    masks included; the same reference with one mask tile toggled does
    not."""
    cell = tiny_cell()
    res = _tiny_run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["reference"]["mask_layers_flipped"] == 0
    ref = cell.reference()
    orig = ref.block_mask_of

    def one_tile_off(bits, nbuckets):
        m = orig(bits, nbuckets)
        return m.at[..., -1, 0].set(~m[..., -1, 0])
    ref.block_mask_of = one_tile_off
    try:
        res2 = _tiny_run(cell)
    finally:
        ref.block_mask_of = orig
    assert not res2["correct"]
    c = res2["checks"]
    assert (c["mask_margin"]["value"] > 10 * c["mask_margin"]["limit"]
            or c["loss_gap"]["value"] > 10 * c["loss_gap"]["limit"]), c


def test_explained_mask_flips_the_bits_nearest_their_planes():
    """A count that one flip of the nearest bit gives is explained by that
    flip and its margin; a count that no flip of the nearest bits gives
    reads 1; no count given reads 0 and keeps the reference's own mask."""
    import jax.numpy as jnp
    ref = tiny_cell().reference()
    bits = jnp.asarray([[0, 0], [1, 0], [0, 1], [1, 1]], bool)
    margins = jnp.asarray([[0.5, 0.4], [0.3, 0.2], [0.6, 0.65], [0.7, 0.01]])
    own = ref.block_mask_of(bits, 4)
    n_own = int(own.sum())              # 4 diagonal + 3 neighbours = 7
    assert n_own == 7
    # flipping block 3's second bit (margin 0.01) puts it in block 1's
    # bucket: tile (3, 1) goes live; so would block 1's second bit (0.2)
    mask, need = ref.explained_mask(bits, margins, 4, jnp.int32(8))
    assert float(need) == pytest.approx(0.01)
    assert bool(mask[3, 1]) and int(mask.sum()) == 8
    mask, need = ref.explained_mask(bits, margins, 4, jnp.int32(n_own))
    assert float(need) == 0.0 and bool((mask == own).all())
    mask, need = ref.explained_mask(bits, margins, 4, jnp.int32(99))
    assert float(need) == 1.0 and bool((mask == own).all())
    mask, need = ref.explained_mask(bits, margins, 4, jnp.int32(-1))
    assert float(need) == 0.0 and bool((mask == own).all())


def _unchanged(batch, lr):
    return batch, lr * 0.0


def _half_batch(batch, lr):
    from bench.calibrate import half_batch
    return half_batch(batch, lr)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "control",
                                   "mask_tile", "kernel_tile"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    """The rest of a run, with the step broken underneath: a step that
    leaves the parameters unchanged, half of the batch left out, the
    system's own bfloat16 path (the control), one tile of the system's hash
    mask toggled in every layer, and the same tile toggled only in the mask
    the attention kernel gets."""
    import contextlib
    from bench import calibrate
    cell = tiny_cell()
    f = {"unchanged": _unchanged, "half_batch": _half_batch}.get(fault)
    if fault == "control":
        cell.cell["parallel"]["param_dtype"] = "bfloat16"
    ctx = calibrate.CONTEXTS.get(fault, contextlib.nullcontext)()
    with ctx:
        res = _tiny_run(cell, fault=f)
    assert not res["correct"], res["checks"]


def test_no_exchange_between_stages_is_not_correct():
    """Two pipeline stages on two host devices: sound, then with the
    stage-to-stage exchange left out."""
    code = textwrap.dedent(f"""
        import os, sys
        sys.path[:0] = [{REPO!r}, {SRC!r}, {os.path.dirname(__file__)!r}]
        import test_bench as tb
        from bench.calibrate import no_exchange
        cell = tb.tiny_cell(stages=2)
        sound = tb._tiny_run(cell)
        with no_exchange():
            broken = tb._tiny_run(tb.tiny_cell(stages=2))
        print("RESULT", sound["correct"], broken["correct"])
    """)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT")]
    assert line == ["RESULT True False"], proc.stdout[-2000:]
