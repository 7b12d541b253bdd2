"""Elastic engine tests: live shrink/grow resharding (paper §3.4).

Host-level tests cover the resplit math (bit-identical round trip); the
subprocess tests run the real multi-device engine: loss parity across an
in-process 4→2 resize and the full 4→2→4 training loop with the controller
deciding the shrink.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import run_in_subprocess

from repro.checkpoint.elastic import (_resplit_stage_tree, elastic_restore,
                                      resplit_indices)
from repro.configs import DistConfig, get_config, reduced_config
from repro.dynamics.config import DynamicsConfig
from repro.models import model as M
from repro.optim.optimizers import OptConfig, make_optimizer


def _setup(stages=4):
    cfg = reduced_config(get_config("smollm-360m"), num_layers=8,
                         d_model=64, d_ff=128)
    dcfg = DistConfig(num_stages=stages, slot_slack=2, remat="none",
                      param_dtype="float32")
    dyncfg = DynamicsConfig()
    params = M.init_params(jax.random.PRNGKey(0), cfg, dcfg)
    dyn = M.init_dyn(cfg, dcfg, dyncfg)
    init_fn, _ = make_optimizer(OptConfig(name="adamw"))
    opt = init_fn(params)
    return cfg, dcfg, dyncfg, params, opt, dyn


def _tree_bitwise_equal(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(fa) == len(fb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(fa, fb))


def test_resplit_indices_cover_all_layers():
    ss, sl, valid = resplit_indices([2, 2, 2, 2], [4, 4], 6)
    assert valid.sum() == 8
    # global order preserved: walking dst slots in order yields src (s, l)
    # in contiguous global order
    got = [(int(ss[s, l]), int(sl[s, l]))
           for s in range(2) for l in range(6) if valid[s, l]]
    want = [(g // 2, g % 2) for g in range(8)]
    assert got == want


def test_shrink_grow_roundtrip_bit_identical():
    """4→2→4 resplit must return bit-identical params, opt moments, and dyn
    state for every live slot (PAD slots are canonically zero)."""
    cfg, dcfg4, dyncfg, params, opt, dyn = _setup(stages=4)
    lps4 = [2, 2, 2, 2]
    L4 = dcfg4.slots_for(cfg)
    dcfg2 = DistConfig(num_stages=2, slot_slack=2, remat="none",
                       param_dtype="float32")

    # normalize: identity resplit zeroes the randomly-initialized PAD slots
    base_stages = _resplit_stage_tree(params["stages"], lps4, lps4, L4)
    base_params = dict(params)
    base_params["stages"] = base_stages
    base_dyn = _resplit_stage_tree(dyn, lps4, lps4, L4)

    p2, o2, d2, _, lps2 = elastic_restore(
        cfg, dcfg4, dcfg2, base_params, opt, base_dyn, lps4)
    p4, o4, d4, _, lps4b = elastic_restore(
        cfg, dcfg2, dcfg4, p2, o2, d2, lps2)

    assert lps4b == lps4
    assert _tree_bitwise_equal(p4["stages"], base_params["stages"])
    assert _tree_bitwise_equal(p4["embed"], base_params["embed"])
    assert _tree_bitwise_equal(d4, base_dyn)
    # optimizer moments follow their layers bit-exactly
    o_base = dict(opt)
    o_base["m"] = dict(opt["m"])
    o_base["m"]["stages"] = _resplit_stage_tree(opt["m"]["stages"], lps4,
                                                lps4, L4)
    o_base["v"] = dict(opt["v"])
    o_base["v"]["stages"] = _resplit_stage_tree(opt["v"]["stages"], lps4,
                                                lps4, L4)
    assert _tree_bitwise_equal(o4["m"]["stages"], o_base["m"]["stages"])
    assert _tree_bitwise_equal(o4["v"]["stages"], o_base["v"]["stages"])
    assert int(o4["count"]) == int(opt["count"])


@pytest.mark.parametrize("stages,slack,lps", [
    (4, 2, None), (2, 0, None), (4, 2, [3, 1, 2, 2])])
def test_init_params_do_not_depend_on_layout(stages, slack, lps):
    """A layer's initial weights come from its global index: every stage
    layout of one seed starts from the model a single stage holds."""
    cfg = reduced_config(get_config("smollm-360m"), num_layers=8,
                         d_model=64, d_ff=128)
    one = M.init_params(jax.random.PRNGKey(0), cfg,
                        DistConfig(num_stages=1, slot_slack=0))
    dcfg = DistConfig(num_stages=stages, slot_slack=slack)
    many = M.init_params(jax.random.PRNGKey(0), cfg, dcfg, lps)
    lps = lps or M.uniform_boundaries(8, stages)
    slots = [(s, l) for s in range(stages) for l in range(lps[s])]
    for name in ("embed", "head", "final_norm"):
        np.testing.assert_array_equal(one[name], many[name])
    for field, a in one["stages"].items():
        for g, (s, l) in enumerate(slots):
            np.testing.assert_array_equal(a[0, g], many["stages"][field][s, l])


def test_resplit_rejects_bad_splits():
    with pytest.raises(AssertionError):
        resplit_indices([2, 2], [3, 2], 4)       # layer count not conserved
    with pytest.raises(AssertionError):
        resplit_indices([2, 2], [4], 3)          # over slot capacity


@pytest.mark.slow
def test_engine_shrink_loss_parity():
    """One engine: the SAME batch must produce the same loss on the 4-stage
    world and, after a live 4→2 resize, on the 2-stage world — and one
    further train step must keep training (finite, updating)."""
    out = run_in_subprocess("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config, reduced_config, DistConfig
from repro.dynamics import DynamicsConfig
from repro.launch.engine import ElasticEngine
from repro.pipeline.pipeline import PipelineShapes

cfg = reduced_config(get_config("smollm-360m"), num_layers=8, d_model=64,
                     num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512)
dcfg = DistConfig(num_stages=4, slot_slack=2, remat="none",
                  param_dtype="float32")
engine = ElasticEngine(cfg, dcfg, DynamicsConfig(),
                       PipelineShapes(2, 2, 32), data=1)
state = engine.init_state(jax.random.PRNGKey(0))
r = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(r.randint(0, cfg.vocab_size, (2, 2, 32)),
                               jnp.int32),
         "labels": jnp.asarray(r.randint(0, cfg.vocab_size, (2, 2, 32)),
                               jnp.int32),
         "label_mask": jnp.ones((2, 2, 32), jnp.float32)}
l4 = float(engine.eval_loss(state, batch))
state2 = engine.resize(state, 2)
l2 = float(engine.eval_loss(state2, batch))
assert abs(l4 - l2) < 3e-3, (l4, l2)
assert engine.pool.num_active == 4        # resize() alone is pool-neutral
loss, _, gnorm = engine.step(state2, batch, jnp.float32(3e-4))
assert np.isfinite(float(loss)) and np.isfinite(float(gnorm))
l2b = float(engine.eval_loss(state2, batch))
assert l2b < l2, (l2b, l2)               # params actually updated
print("PASS", l4, l2, l2b)
""", devices=4, timeout=900)
    assert "PASS" in out


@pytest.mark.slow
def test_engine_evict_failure_path():
    """A mid-list worker failure: the engine rebuilds without it, the loss
    is preserved, and the job manager records it dead (not released — it
    is not grantable until revived on the manager side)."""
    out = run_in_subprocess("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config, reduced_config, DistConfig
from repro.dynamics import DynamicsConfig
from repro.launch.engine import ElasticEngine
from repro.pipeline.pipeline import PipelineShapes

cfg = reduced_config(get_config("smollm-360m"), num_layers=8, d_model=64,
                     num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512)
dcfg = DistConfig(num_stages=4, slot_slack=2, remat="none",
                  param_dtype="float32")
engine = ElasticEngine(cfg, dcfg, DynamicsConfig(),
                       PipelineShapes(2, 2, 32), data=1)
state = engine.init_state(jax.random.PRNGKey(0))
r = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(r.randint(0, cfg.vocab_size, (2, 2, 32)),
                               jnp.int32),
         "labels": jnp.asarray(r.randint(0, cfg.vocab_size, (2, 2, 32)),
                               jnp.int32),
         "label_mask": jnp.ones((2, 2, 32), jnp.float32)}
l4 = float(engine.eval_loss(state, batch))
epoch0 = engine.epoch
state3 = engine.evict(state, [1], step=7)
assert engine.epoch == epoch0 + 1          # resize fenced the epoch
assert engine.stage_workers == [0, 2, 3]
assert engine.pool.dead == {1} and not engine.pool.released
assert engine.pool.num_active == 3
assert engine.jm.request(1) == []          # dead workers are not grantable
l3 = float(engine.eval_loss(state3, batch))
assert abs(l4 - l3) < 3e-3, (l4, l3)
rz = engine.resizes[-1]
assert rz.kind == "evict" and rz.workers == [1] and rz.step == 7
assert engine.evict(state3, [9]) is state3   # unknown worker: no-op
print("PASS", l4, l3)
""", devices=4, timeout=900)
    assert "PASS" in out


@pytest.mark.slow
def test_engine_live_shrink_grow_in_training_loop():
    """The acceptance demo: pruning shrinks the model, the controller's
    repack decision triggers a live 4→2 shrink mid-run (released workers
    reported via the WorkerPool), --grow-back re-expands to 4; the loss
    keeps descending across both resizes."""
    out = run_in_subprocess("""
from repro.launch.train import run_training
out = run_training("smollm-360m", steps=26, stages=4, layers=8, d_model=128,
                   seq=32, num_micro=4, mb_global=2, dynamism="pruning",
                   repack=True, grow_back=6, rebalance_every=5,
                   log_every=1000)
rz = out["resizes"]
assert len(rz) == 2, rz
assert rz[0]["kind"] == "shrink" and rz[0]["from_stages"] == 4 \
    and rz[0]["to_stages"] == 2, rz
assert rz[1]["kind"] == "grow" and rz[1]["to_stages"] == 4, rz
assert rz[0]["ticks_after"] < rz[0]["ticks_before"], rz
assert set(rz[0]["workers"]) == set(rz[1]["workers"]) == {2, 3}, rz
assert out["pool_log"] == ["release:2", "release:3", "grant:2", "grant:3"], \
    out["pool_log"]
assert out["final_stages"] == 4
assert 2 in out["stages_history"] and 4 in out["stages_history"]
import math
assert all(math.isfinite(l) for l in out["losses"])
# loss continues descending through both resizes (compare window means)
pre = out["losses"][:rz[0]["step"]]
post = out["losses"][rz[0]["step"] + 1:]
assert sum(post) / len(post) < sum(pre) / len(pre), (pre, post)
print("PASS", out["losses"][0], "->", out["losses"][-1])
""", devices=4, timeout=900)
    assert "PASS" in out


@pytest.mark.parametrize("kind", ["sparse_attention", "none"])
def test_engine_counts_the_attention_tiles_of_its_steps(kind):
    """``ElasticEngine.attn_tiles_total`` sums each layer's live tiles over
    the steps' sequences exactly.  Sparse: the mask sum of
    ``hash_block_mask``, which each layer's density reports (the density
    stat sums, over micro-batches, the mean over rows of the mask sum over
    its causal tiles).  Dense: the Pallas kernels run the tiles
    ``choose_attention_tiles`` gives the shape and skip those wholly above
    the diagonal: nb(nb + 1)/2 a sequence at square tiles."""
    from repro.data.loader import DataConfig, make_loader
    from repro.kernels.block_sparse_attention import choose_attention_tiles
    from repro.launch.engine import ElasticEngine
    from repro.pipeline.pipeline import PipelineShapes
    layers, num_micro, mb, seq, block, steps = 2, 2, 2, 256, 64, 2
    cfg = reduced_config(get_config("smollm-360m"), num_layers=layers,
                         d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                         vocab_size=128)
    dcfg = DistConfig(num_stages=1, slot_slack=0, remat="block",
                      param_dtype="float32", kernel_impl="pallas")
    engine = ElasticEngine(
        cfg, dcfg, DynamicsConfig(kind=kind, sparse_block=block,
                                  sparse_nbuckets=4),
        PipelineShapes(num_micro=num_micro, mb_global=mb, seq=seq))
    state = engine.init_state(jax.random.PRNGKey(0))
    loader = make_loader(cfg, DataConfig(num_micro, mb, seq, seed=0))
    want = 0
    for _ in range(steps):
        _, stats, _ = engine.step(state, next(loader), jnp.float32(1e-3))
        if kind == "sparse_attention":
            nb = seq // block
            want += int(np.rint(np.asarray(stats["attn_density"])
                                * nb * (nb + 1) / 2 * mb).sum())
        else:
            bq, bk = choose_attention_tiles(seq, seq, cfg.resolved_head_dim,
                                            True, 4)
            nq, nk = -(-seq // bq), -(-seq // bk)
            want += layers * num_micro * mb * sum(
                min(nk, (i * bq + bq - 1) // bk + 1) for i in range(nq))
    assert engine.attn_tiles_total() == want
    assert engine.attn_tiles_total() == want    # folded once, not twice
