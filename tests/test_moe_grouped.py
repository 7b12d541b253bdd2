"""Expert-parallel MoE: grouped ragged Pallas matmul + live expert re-layout.

Local tests pin the grouped kernel (fwd AND grads) against the fp32
capacity-einsum oracle at the ragged corner cases — empty experts, one
expert taking every token, counts not a multiple of the row tile — and pin
``moe_ffn``'s pallas path to the scan/capacity path (same routing, same
drops, same grads).  Placement neutrality (the invariant that makes live
re-layout restart-free) is asserted bitwise.  Subprocess tests run the
real multi-device engine: expert_map rides a 4→2→4 resize, and (on modern
jax) a Session train with re-layout ON matches re-layout OFF loss-for-loss.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import run_in_subprocess

from repro.checkpoint.elastic import _resplit_stage_tree, elastic_restore
from repro.configs import DistConfig, get_config, reduced_config
from repro.core import expert_layout as el
from repro.core.controller import ControllerConfig, DynMoController
from repro.core.cost_model import LayerDynState
from repro.core.profiler import LayerProfile
from repro.dynamics.config import DynamicsConfig
from repro.kernels.grouped_matmul import (grouped_matmul, grouped_matmul_ref,
                                          grouped_tile_work)
from repro.models import model as M
from repro.models.blocks import moe_ffn

def _moe_cfg(capacity_factor=1.0):
    cfg = reduced_config(get_config("mixtral-8x7b"), num_layers=4,
                         d_model=64, d_ff=128)
    import dataclasses
    return dataclasses.replace(cfg, moe_capacity_factor=capacity_factor)


def _moe_params(rng, cfg, d, ff):
    E = cfg.num_experts
    return {
        "router": jnp.asarray(rng.randn(d, E) * 0.4, jnp.float32),
        "ewi": jnp.asarray(rng.randn(E, d, ff) * 0.2, jnp.float32),
        "ewg": jnp.asarray(rng.randn(E, d, ff) * 0.2, jnp.float32),
        "ewo": jnp.asarray(rng.randn(E, ff, d) * 0.2, jnp.float32),
    }


# ---------------------------------------------------------------------------
# grouped kernel vs fp32 oracle
# ---------------------------------------------------------------------------

# G=8 groups over E=4 experts; cap=20 is NOT a multiple of bm=8, K=96 and
# N=72 are NOT multiples of bk/bn=128 (both padding paths exercised)
_KERNEL_CASES = {
    "uniform": [10, 10, 10, 10, 10, 10, 10, 10],
    "empty_experts": [20, 0, 7, 0, 0, 13, 0, 0],
    "one_takes_all": [20, 0, 0, 0, 20, 0, 0, 0],
    "all_empty": [0, 0, 0, 0, 0, 0, 0, 0],
    "ragged": [1, 19, 3, 8, 20, 0, 5, 2],
}


@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
def test_grouped_matmul_matches_oracle(case):
    rng = np.random.RandomState(0)
    G, cap, K, N, E = 8, 20, 96, 72, 4
    x = jnp.asarray(rng.randn(G, cap, K) * 0.3, jnp.float32)
    w = jnp.asarray(rng.randn(E, K, N) * 0.3, jnp.float32)
    counts = jnp.asarray(_KERNEL_CASES[case], jnp.int32)
    out = grouped_matmul(x, w, counts, interpret=True)
    ref = grouped_matmul_ref(x, w, counts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # dead rows are zero by contract, regardless of input garbage there
    live = np.arange(cap)[None, :] < np.asarray(counts)[:, None]
    assert np.all(np.asarray(out)[~live] == 0.0)
    garbage = x + jnp.asarray(~live[..., None] * 1e6, jnp.float32)
    out_g = grouped_matmul(garbage, w, counts, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_g), np.asarray(out))


@pytest.mark.parametrize("case",
                         ["uniform", "empty_experts", "one_takes_all",
                          "ragged"])
def test_grouped_matmul_grads_match_oracle(case):
    rng = np.random.RandomState(1)
    G, cap, K, N, E = 8, 20, 96, 72, 4
    x = jnp.asarray(rng.randn(G, cap, K) * 0.3, jnp.float32)
    w = jnp.asarray(rng.randn(E, K, N) * 0.3, jnp.float32)
    cot = jnp.asarray(rng.randn(G, cap, N) * 0.3, jnp.float32)
    counts = jnp.asarray(_KERNEL_CASES[case], jnp.int32)

    def loss(fn):
        return lambda x, w: jnp.sum(fn(x, w, counts) * cot)

    gk = jax.grad(loss(lambda *a: grouped_matmul(*a, interpret=True)),
                  argnums=(0, 1))(x, w)
    gr = jax.grad(loss(grouped_matmul_ref), argnums=(0, 1))(x, w)
    for got, want, name in zip(gk, gr, ("dx", "dw")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    # empty experts pay zero tile work, fwd and bwd
    work = grouped_tile_work(_KERNEL_CASES[case], cap)
    dense = grouped_tile_work([cap] * G, cap)
    assert work["fwd_total"] == dense["fwd_total"]
    if case != "uniform":
        assert work["fwd_active"] < work["fwd_total"]
        assert work["bwd_active"] < work["bwd_total"]


# ---------------------------------------------------------------------------
# moe_ffn: grouped path == capacity path (routing, drops, grads)
# ---------------------------------------------------------------------------

def test_moe_ffn_pallas_matches_scan():
    cfg = _moe_cfg(capacity_factor=1.0)    # tight capacity -> real drops
    rng = np.random.RandomState(2)
    b, s, d, ff = 2, 32, cfg.d_model, cfg.d_ff
    p = _moe_params(rng, cfg, d, ff)
    x = jnp.asarray(rng.randn(b, s, d) * 0.5, jnp.float32)
    y_s, load_s, aux_s, drop_s = moe_ffn(p, x, cfg, kernel_impl="scan")
    y_p, load_p, aux_p, drop_p = moe_ffn(p, x, cfg, kernel_impl="pallas")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_s),
                               rtol=1e-5, atol=1e-6)
    # routing is shared across impls: load / aux / drop are EXACT
    np.testing.assert_array_equal(np.asarray(load_p), np.asarray(load_s))
    assert float(aux_p) == float(aux_s)
    assert float(drop_p) == float(drop_s)
    assert float(drop_s) > 0.0             # the tight capacity actually drops

    def total(p, impl):
        y, _, aux, _ = moe_ffn(p, x, cfg, kernel_impl=impl)
        return jnp.sum(y ** 2) + aux       # router grads via aux too

    gs = jax.grad(lambda p: total(p, "scan"))(p)
    gp = jax.grad(lambda p: total(p, "pallas"))(p)
    for k in sorted(p):
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gs[k]),
                                   rtol=2e-4, atol=1e-5, err_msg=k)


def test_moe_ffn_decode_token_identity():
    """s == 1 (the serving decode shape) takes the grouped path too and
    must agree with the capacity oracle."""
    cfg = _moe_cfg(capacity_factor=4.0)
    rng = np.random.RandomState(3)
    p = _moe_params(rng, cfg, cfg.d_model, cfg.d_ff)
    x = jnp.asarray(rng.randn(4, 1, cfg.d_model) * 0.5, jnp.float32)
    y_s = moe_ffn(p, x, cfg, kernel_impl="scan")[0]
    y_p = moe_ffn(p, x, cfg, kernel_impl="pallas")[0]
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_s),
                               rtol=1e-5, atol=1e-6)


def test_expert_map_placement_is_bit_neutral():
    """Any expert placement computes the same y BITWISE — the invariant
    that lets a live re-layout run mid-training with zero loss impact."""
    cfg = _moe_cfg(capacity_factor=1.0)
    rng = np.random.RandomState(4)
    E = cfg.num_experts
    p = _moe_params(rng, cfg, cfg.d_model, cfg.d_ff)
    x = jnp.asarray(rng.randn(2, 32, cfg.d_model) * 0.5, jnp.float32)
    base = moe_ffn(p, x, cfg, kernel_impl="pallas")
    for perm in ([1, 0, 3, 2], [3, 2, 1, 0], [2, 0, 3, 1]):
        em = jnp.asarray(perm, jnp.float32)
        got = moe_ffn(p, x, cfg, kernel_impl="pallas", expert_map=em)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(base[0]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(base[1]))
        assert float(got[3]) == float(base[3])
    assert E == 4


# ---------------------------------------------------------------------------
# expert layout / re-layout planning (pure host)
# ---------------------------------------------------------------------------

def test_build_relayout_interleaves_hot_and_cold():
    cur = el.ExpertLayout.identity(4)
    plan = el.build_relayout([100, 2, 3, 1], cur, watermark=2.0,
                             min_tokens=16, iteration=7)
    assert plan is not None and plan.iteration == 7
    # hot->cold ranking [0,2,1,3] zipped from both ends: physical order
    # (hot, coldest, 2nd-hot, 2nd-cold) = logical experts (0, 3, 2, 1)
    assert plan.new.placement == (0, 3, 2, 1)
    assert plan.moved_experts == 2
    assert plan.skew == pytest.approx(100 / 26.5)
    # guards: window too small / skew under watermark / already placed
    assert el.build_relayout([100, 2, 3, 1], cur, watermark=2.0,
                             min_tokens=1000, iteration=0) is None
    assert el.build_relayout([10, 9, 11, 10], cur, watermark=2.0,
                             min_tokens=1, iteration=0) is None
    assert el.build_relayout([100, 2, 3, 1], plan.new, watermark=2.0,
                             min_tokens=16, iteration=8) is None


def test_expert_migration_roundtrip_bit_identical():
    """A re-layout is the standard migration gather over a [1, E] grid;
    applying plan then its inverse restores every per-expert leaf bitwise."""
    rng = np.random.RandomState(5)
    old = el.ExpertLayout.identity(4)
    new = el.ExpertLayout((2, 0, 3, 1), (1.0,) * 4)
    tree = {"a": jnp.asarray(rng.randn(4, 3, 5), jnp.float32),
            "b": jnp.asarray(rng.randn(4), jnp.float32)}
    fwd = el.apply_expert_plan(tree, el.as_migration_plan(old, new))
    # physical slot p now holds the state of logical expert new.inverse[p]
    inv = np.asarray(new.inverse)
    np.testing.assert_array_equal(np.asarray(fwd["a"]),
                                  np.asarray(tree["a"])[inv])
    back = el.apply_expert_plan(fwd, el.as_migration_plan(new, old))
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))


def test_controller_relayout_decision_flow():
    """decide() only stages a plan; the layout advances at commit (safe
    point), and a rebind (elastic resize) preserves it."""
    cfg = _moe_cfg()
    dcfg = DistConfig(num_stages=2, slot_slack=2, remat="none",
                      param_dtype="float32")
    dyncfg = DynamicsConfig(kind="moe")
    ctrl = DynMoController(cfg, dcfg, dyncfg, ControllerConfig(
        method="partition", rebalance_every=1, expert_relayout=True,
        expert_watermark=1.5, expert_min_tokens=8))
    assert ctrl.expert_layout == el.ExpertLayout.identity(cfg.num_experts)
    L = cfg.total_blocks()
    prof = LayerProfile(
        time_per_layer=np.ones(L), param_bytes=np.ones(L),
        mem_per_stage=np.zeros(2),
        dyn_states=[LayerDynState() for _ in range(L)],
        expert_load=np.asarray([100.0, 2.0, 3.0, 1.0]),
        moe_drop_frac=0.125)
    _, ev = ctrl.decide(prof, 5)
    assert ev.relayout and ev.expert_skew > 1.5
    assert ev.expert_dropped == 0.125
    plan = ctrl.take_expert_relayout()
    assert plan is not None and ctrl.take_expert_relayout() is None
    assert ctrl.expert_layout.placement == plan.old.placement  # not yet
    ctrl.commit_relayout(plan)
    assert ctrl.expert_layout.placement == plan.new.placement
    assert len(ctrl.relayouts) == 1
    ctrl.rebind(dcfg, ctrl.lps)
    assert ctrl.expert_layout.placement == plan.new.placement
    # balanced load on the new layout: telemetry still flows, no new plan
    prof2 = LayerProfile(
        time_per_layer=np.ones(L), param_bytes=np.ones(L),
        mem_per_stage=np.zeros(2),
        dyn_states=[LayerDynState() for _ in range(L)],
        expert_load=np.asarray([26.0, 27.0, 26.0, 27.0]))
    _, ev2 = ctrl.decide(prof2, 6)
    assert not ev2.relayout and ev2.expert_skew == pytest.approx(27 / 26.5)


def test_expert_map_survives_elastic_resplit():
    """The expert_map dyn leaf rides the 4→2→4 stage resplit bit-exactly
    like every other [S, L_max] leaf (host-level resplit math)."""
    cfg = _moe_cfg()
    dcfg4 = DistConfig(num_stages=4, slot_slack=2, remat="none",
                       param_dtype="float32")
    dcfg2 = DistConfig(num_stages=2, slot_slack=2, remat="none",
                       param_dtype="float32")
    dyncfg = DynamicsConfig(kind="moe", expert_relayout=True)
    dyn = M.init_dyn(cfg, dcfg4, dyncfg)
    assert "expert_map" in dyn and dyn["expert_map"].shape[-1] == 4
    # a committed non-identity placement, mirrored into every live slot
    dyn = dict(dyn)
    dyn["expert_map"] = (dyn["expert_map"] * 0
                         + jnp.asarray([2.0, 0.0, 3.0, 1.0]))
    lps4 = [1, 1, 1, 1]
    base = _resplit_stage_tree(dyn, lps4, lps4, dcfg4.slots_for(cfg))
    params = M.init_params(jax.random.PRNGKey(0), cfg, dcfg4)
    _, _, d2, _, lps2 = elastic_restore(cfg, dcfg4, dcfg2, params, None,
                                        base, lps4)
    assert d2["expert_map"].shape == (2, dcfg2.slots_for(cfg), 4)
    _, _, d4, _, lps4b = elastic_restore(cfg, dcfg2, dcfg4, params, None,
                                         d2, lps2)
    assert lps4b == lps4
    np.testing.assert_array_equal(np.asarray(d4["expert_map"]),
                                  np.asarray(base["expert_map"]))


# ---------------------------------------------------------------------------
# multi-device integration (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_moe_relayout_and_resize():
    """Real 4-device engine on the grouped pallas path (forward-only, so it
    runs on every jax): a live re-layout leaves the eval loss bit-identical,
    and the committed placement survives a 4→2→4 resize."""
    out = run_in_subprocess("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config, reduced_config, DistConfig
from repro.core import expert_layout as el
from repro.dynamics import DynamicsConfig
from repro.launch.engine import ElasticEngine
from repro.pipeline.pipeline import PipelineShapes

cfg = reduced_config(get_config("mixtral-8x7b"), num_layers=4, d_model=64,
                     d_ff=128)
dcfg = DistConfig(num_stages=4, slot_slack=2, remat="none",
                  param_dtype="float32", kernel_impl="pallas")
dyncfg = DynamicsConfig(kind="moe", expert_relayout=True)
engine = ElasticEngine(cfg, dcfg, dyncfg, PipelineShapes(2, 2, 32), data=1)
state = engine.init_state(jax.random.PRNGKey(0), with_opt=False)
assert "expert_map" in state.dyn
r = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(r.randint(0, cfg.vocab_size, (2, 2, 32)),
                               jnp.int32),
         "labels": jnp.asarray(r.randint(0, cfg.vocab_size, (2, 2, 32)),
                               jnp.int32),
         "label_mask": jnp.ones((2, 2, 32), jnp.float32)}
l0 = float(engine.eval_loss(state, batch))
# live re-layout at a safe point: only the expert_map dyn leaf moves
plan = el.build_relayout([90, 4, 5, 1], el.ExpertLayout.identity(4),
                         watermark=1.5, min_tokens=8, iteration=1)
assert plan is not None and plan.new.placement != (0, 1, 2, 3)
dyn = dict(state.dyn)
dyn["expert_map"] = (dyn["expert_map"] * 0
                     + jnp.asarray(plan.new.as_array()))
state.dyn = dyn
l1 = float(engine.eval_loss(state, batch))
assert l1 == l0, (l0, l1)                 # placement is bit-neutral
state2 = engine.resize(state, 2)
l2 = float(engine.eval_loss(state2, batch))
assert abs(l2 - l0) < 3e-3, (l0, l2)
state4 = engine.resize(state2, 4)
l4 = float(engine.eval_loss(state4, batch))
assert abs(l4 - l0) < 3e-3, (l0, l4)
em = np.asarray(state4.dyn["expert_map"])
S, L_max = em.shape[:2]
# every live slot still carries the committed placement after 4->2->4
tags = np.asarray(cfg.block_pattern())
from repro.configs.base import BLOCK_MOE
want = np.asarray(plan.new.placement, np.float32)
live = 0
for s_ in range(S):
    for l_ in range(L_max):
        if np.any(em[s_, l_] != 0):
            assert np.array_equal(em[s_, l_], want), (s_, l_, em[s_, l_])
            live += 1
assert live == int(np.sum(tags == BLOCK_MOE)), (live, tags)
print("PASS", l0, l2, l4)
""", devices=4, timeout=900)
    assert "PASS" in out


@pytest.mark.slow
def test_session_relayout_is_loss_neutral():
    """The acceptance demo: a full Session train on the moe scenario with
    live re-layout ON fires at least one re-layout and produces the SAME
    loss sequence as re-layout OFF — no restart, no perturbation."""
    out = run_in_subprocess("""
import dataclasses
from repro.api.scenarios import scenario
from repro.api.session import Session

sp = scenario("moe")
sp = dataclasses.replace(
    sp, steps=12,
    parallel=dataclasses.replace(sp.parallel, kernel_impl="pallas"),
    dynamics=dataclasses.replace(sp.dynamics, expert_relayout=True,
                                 expert_watermark=1.01,
                                 expert_min_tokens=1))
with Session(sp) as s:
    on = s.train()
off_dyn = dataclasses.replace(sp.dynamics, expert_relayout=False)
with Session(dataclasses.replace(sp, dynamics=off_dyn)) as s:
    off = s.train()
assert len(on["relayouts"]) >= 1, on["relayouts"]
assert on["relayouts"][0]["moved_experts"] > 0
assert on["expert_layout"] is not None \\
    and on["expert_layout"] != [0, 1, 2, 3]
assert on["losses"] == off["losses"], (on["losses"], off["losses"])
assert on["expert_skew_last"] is not None and on["expert_skew_last"] >= 1.0
print("PASS", len(on["relayouts"]), on["expert_layout"])
""", devices=4, timeout=900)
    assert "PASS" in out


@pytest.mark.slow
def test_serve_moe_drop_telemetry():
    """Serving a MoE arch on the grouped path surfaces the capacity-drop
    fraction in the serve report (forward-only: runs on every jax)."""
    out = run_in_subprocess("""
from repro.api.session import Session
from repro.launch.serve import serve_spec

spec = serve_spec("mixtral-8x7b", stages=4, micro=2, mb_global=2,
                  prompt_len=8, gen=6, layers=4, d_model=64, requests=4,
                  kernel_impl="pallas")
with Session(spec) as s:
    rep = s.serve()
assert len(rep["completions"]) == 4, rep["completions"]
assert rep["moe_dropped_mean"] is not None
assert 0.0 <= rep["moe_dropped_mean"] < 1.0
print("PASS", rep["moe_dropped_mean"])
""", devices=4, timeout=900)
    assert "PASS" in out
