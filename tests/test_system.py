"""End-to-end system tests (multi-device, subprocess-isolated so the main
pytest process keeps 1 device).

These are the heavyweight integration gates:
  * pipelined loss == single-device sequential reference (with grads),
  * live rebalancing mid-training preserves the loss math (no recompile),
  * prefill + decode == incremental full-forward,
  * mini multi-pod dry-run (AOT lower/compile on a (2,2,2) mesh with the
    production sharding rules — same code path as the 512-chip dry-run).
"""
import pytest

from conftest import run_in_subprocess

@pytest.mark.slow
@pytest.mark.parametrize("arch", [
    "smollm-360m",
    "mixtral-8x7b",
])
def test_pipeline_equals_reference(arch):
    out = run_in_subprocess("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config, reduced_config, DistConfig
from repro.dynamics import DynamicsConfig
from repro.models import model as M
from repro.pipeline.pipeline import PipelineShapes, build_loss_fn

from repro.launch.mesh import _auto_mesh
mesh = _auto_mesh((2, 4), ("data", "model"))
for arch in (__ARCH__,):
    cfg = reduced_config(get_config(arch), num_layers=6)
    dcfg = DistConfig(num_stages=4, slot_slack=1, remat="none",
                      param_dtype="float32")
    dyncfg = DynamicsConfig()
    params = M.init_params(jax.random.PRNGKey(0), cfg, dcfg)
    assignment = M.make_assignment(cfg, dcfg)
    dyn = M.init_dyn(cfg, dcfg, dyncfg)
    micro, mbg, seq = 4, 4, 32
    r = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(r.randint(0, cfg.vocab_size,
                                             (micro, mbg, seq)), jnp.int32),
             "labels": jnp.asarray(r.randint(0, cfg.vocab_size,
                                             (micro, mbg, seq)), jnp.int32),
             "label_mask": jnp.ones((micro, mbg, seq), jnp.float32)}
    loss_fn = build_loss_fn(cfg, dcfg, dyncfg, mesh,
                            PipelineShapes(micro, mbg, seq))
    with mesh:
        loss, stats = jax.jit(loss_fn)(params, assignment, dyn, batch)
        g = jax.jit(jax.grad(
            lambda p: loss_fn(p, assignment, dyn, batch)[0]))(params)
    ref = M.reference_loss(cfg, dcfg, dyncfg, params, assignment, dyn,
                           batch["tokens"].reshape(-1, seq),
                           batch["labels"].reshape(-1, seq))
    assert abs(float(loss) - float(ref)) < 3e-3, (arch, loss, ref)
    gs = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(gs) and gs > 0
    print(arch, "OK", float(loss))
print("PASS")
""".replace("__ARCH__", repr(arch)), devices=8, timeout=900)
    assert "PASS" in out


@pytest.mark.slow
def test_live_rebalance_preserves_training_math():
    """Migrate to a skewed split mid-run; the jitted loss (NOT recompiled)
    must produce the identical value — DynMo's 'no accuracy impact'."""
    out = run_in_subprocess("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config, reduced_config, DistConfig
from repro.dynamics import DynamicsConfig
from repro.models import model as M
from repro.core.controller import ControllerConfig, DynMoController
from repro.pipeline.pipeline import PipelineShapes, build_loss_fn

from repro.launch.mesh import _auto_mesh
mesh = _auto_mesh((2, 4), ("data", "model"))
cfg = reduced_config(get_config("smollm-360m"), num_layers=8)
dcfg = DistConfig(num_stages=4, slot_slack=3, remat="none",
                  param_dtype="float32")
dyncfg = DynamicsConfig()
params = M.init_params(jax.random.PRNGKey(0), cfg, dcfg)
assignment = M.make_assignment(cfg, dcfg)
dyn = M.init_dyn(cfg, dcfg, dyncfg)
micro, mbg, seq = 4, 4, 32
r = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(r.randint(0, cfg.vocab_size,
                                         (micro, mbg, seq)), jnp.int32),
         "labels": jnp.asarray(r.randint(0, cfg.vocab_size,
                                         (micro, mbg, seq)), jnp.int32),
         "label_mask": jnp.ones((micro, mbg, seq), jnp.float32)}
loss_fn = jax.jit(build_loss_fn(cfg, dcfg, dyncfg, mesh,
                                PipelineShapes(micro, mbg, seq)))
with mesh:
    l1, _ = loss_fn(params, assignment, dyn, batch)
    ctrl = DynMoController(cfg, dcfg, dyncfg,
                           ControllerConfig(method="partition"))
    params2, _, dyn2, assignment2, _ = ctrl.apply([1, 2, 2, 3], params,
                                                  None, dyn)
    l2, _ = loss_fn(params2, assignment2, dyn2, batch)
assert abs(float(l1) - float(l2)) < 3e-3, (float(l1), float(l2))
print("PASS", float(l1), float(l2))
""", devices=8, timeout=900)
    assert "PASS" in out


@pytest.mark.slow
def test_serve_prefill_decode_consistency():
    out = run_in_subprocess("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config, reduced_config, DistConfig
from repro.dynamics import DynamicsConfig
from repro.models import model as M
from repro.models import blocks as B
from repro.pipeline.pipeline import (PipelineShapes, build_decode_fn,
                                     build_prefill_fn)

from repro.launch.mesh import _auto_mesh
mesh = _auto_mesh((2, 4), ("data", "model"))
cfg = reduced_config(get_config("smollm-360m"), num_layers=6)
dcfg = DistConfig(num_stages=4, slot_slack=1, remat="none",
                  param_dtype="float32")
dyncfg = DynamicsConfig()
params = M.init_params(jax.random.PRNGKey(0), cfg, dcfg)
assignment = M.make_assignment(cfg, dcfg)
dyn = M.init_dyn(cfg, dcfg, dyncfg)
micro, mbg, seq, gen = 2, 4, 16, 3
shapes = PipelineShapes(micro, mbg, seq, cache_len=seq + gen)
r = np.random.RandomState(0)
tokens = jnp.asarray(r.randint(0, cfg.vocab_size, (micro, mbg, seq)),
                     jnp.int32)
cache = M.init_cache(cfg, dcfg, micro, mbg, seq + gen)
prefill = jax.jit(build_prefill_fn(cfg, dcfg, dyncfg, mesh, shapes))
decode = jax.jit(build_decode_fn(cfg, dcfg, dyncfg, mesh, shapes))
with mesh:
    ids0, cache, _ = prefill(params, assignment, dyn, cache,
                             {"tokens": tokens})
    seqs = [np.asarray(ids0)]
    toks = ids0
    for g in range(1, gen):
        ids, lp, cache, _ = decode(params, assignment, dyn, cache, toks,
                                   jnp.int32(seq + g - 1))
        seqs.append(np.asarray(ids))
        toks = ids

def ref_next(tok_full):
    carry = M.embed(params, cfg, tok_full)
    pos = jnp.arange(carry["x"].shape[1])
    tags = np.asarray(assignment["tags"])
    for s in range(tags.shape[0]):
        for l in range(tags.shape[1]):
            if tags[s, l] == 0:
                continue
            p = jax.tree.map(lambda a: a[s, l], params["stages"])
            ds = jax.tree.map(lambda a: a[s, l], dyn)
            carry, _, _, _ = B.apply_block(cfg, dyncfg, "train", p,
                                           params["shared"], carry,
                                           jnp.int32(tags[s, l]), ds, None,
                                           pos)
    return np.asarray(jnp.argmax(
        M.lm_logits(params, cfg, carry["x"][:, -1]), -1).astype(jnp.int32))

for mi in range(micro):
    tf = tokens[mi]
    for g in range(gen):
        want = ref_next(tf)
        got = seqs[g][mi]
        assert (want == got).all(), (mi, g, want[:4], got[:4])
        tf = jnp.concatenate([tf, want[:, None].astype(jnp.int32)], axis=1)
print("PASS")
""", devices=8, timeout=900)
    assert "PASS" in out


@pytest.mark.slow
def test_mini_multipod_dryrun():
    out = run_in_subprocess("""
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_config, reduced_config, DistConfig
from repro.dynamics import DynamicsConfig
from repro.models import model as M
from repro.launch import sharding as SH
from repro.launch.train import make_train_step
from repro.optim.optimizers import OptConfig, make_optimizer
from repro.pipeline.pipeline import PipelineShapes

from repro.launch.mesh import _auto_mesh
mesh = _auto_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = reduced_config(get_config("mixtral-8x7b"), num_layers=4, d_model=64,
                     num_heads=4, num_kv_heads=2, d_ff=256)
dcfg = DistConfig(num_stages=2, slot_slack=1, remat="full",
                  param_dtype="bfloat16")
dyncfg = DynamicsConfig()
shapes = PipelineShapes(num_micro=2, mb_global=4, seq=32)
pspec = M.param_spec(cfg, dcfg)
pshard = SH.param_shardings(cfg, dcfg, mesh, pspec)
params_sds = SH.attach(pspec, pshard)
aspec = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     M.make_assignment(cfg, dcfg))
assign_sds = SH.attach(aspec, SH.stage_tree_shardings(aspec, mesh))
dspec = M.dyn_spec(cfg, dcfg, dyncfg)
dyn_sds = SH.attach(dspec, SH.stage_tree_shardings(dspec, mesh))
init_fn, _ = make_optimizer(OptConfig(name="adamw"))
opt_t = jax.eval_shape(init_fn, pspec)
opt_sds = SH.attach(opt_t, SH.opt_shardings(opt_t, pshard, mesh))
batch_spec = {
    "tokens": jax.ShapeDtypeStruct((2, 4, 32), jnp.int32),
    "labels": jax.ShapeDtypeStruct((2, 4, 32), jnp.int32),
    "label_mask": jax.ShapeDtypeStruct((2, 4, 32), jnp.float32)}
batch_sds = SH.attach(batch_spec, SH.batch_shardings(batch_spec, mesh))
_, step = make_train_step(cfg, dcfg, dyncfg, mesh, shapes)
lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
    params_sds, opt_sds, assign_sds, dyn_sds, batch_sds,
    jax.ShapeDtypeStruct((), jnp.float32))
compiled = lowered.compile()
ma = compiled.memory_analysis()
assert ma.temp_size_in_bytes > 0
import re
colls = re.findall(r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                   r"collective-permute)", compiled.as_text())
assert "collective-permute" in colls   # the pipeline ring exists
print("PASS", sorted(set(colls)))
""", devices=8, timeout=900)
    assert "PASS" in out


@pytest.mark.slow
@pytest.mark.parametrize("dynamism", ["sparse_attention", "pruning"])
def test_training_loop_pallas_kernels(dynamism):
    """End-to-end pipelined training through kernel_impl="pallas": the
    block-skipping Pallas kernels (interpret mode on CPU) carry the real
    forward AND backward for attention + SwiGLU under both dynamism schemes.
    sparse_block is shrunk so the hash mask actually fires at toy seq."""
    out = run_in_subprocess(f"""
from repro.launch.train import run_training
out = run_training("smollm-360m", steps=6, stages=2, layers=4, d_model=64,
                   seq=32, num_micro=2, mb_global=2,
                   dynamism={dynamism!r}, kernel_impl="pallas",
                   dyn_overrides=dict(sparse_block=16, sparse_nbuckets=4),
                   rebalance_every=3, log_every=100)
import math
assert all(math.isfinite(l) for l in out["losses"]), out["losses"]
assert out["losses"][-1] < out["losses"][0] + 0.5, out["losses"]
print("PASS", out["losses"][0], "->", out["losses"][-1])
""", devices=2, timeout=900)
    assert "PASS" in out


@pytest.mark.slow
def test_training_loop_with_dynmo_controller():
    """Real training with the full DynMo loop: loss descends, pruning fires,
    checkpoints restore."""
    out = run_in_subprocess("""
from repro.launch.train import run_training
out = run_training("smollm-360m", steps=22, stages=4, layers=8, d_model=64,
                   seq=32, num_micro=2, mb_global=2, dynamism="pruning",
                   rebalance_every=5, log_every=100)
assert out["losses"][-1] < out["losses"][0], out["losses"][:3]
print("PASS", out["losses"][0], "->", out["losses"][-1])
""", devices=4, timeout=900)
    assert "PASS" in out
