"""Fault-tolerance runtime + gradient compression tests."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.runtime.compression import (compress_topk, decompress_topk,
                                       int8_dequantize, int8_quantize)
from repro.runtime.fault_tolerance import (HeartbeatMonitor,
                                           StragglerDetector, WorkerPool)


def test_heartbeat_detects_failure():
    t = [0.0]
    mon = HeartbeatMonitor(4, timeout_s=10.0, clock=lambda: t[0])
    t[0] = 5.0
    for w in range(3):
        mon.beat(w)
    t[0] = 12.0
    assert mon.failed_workers() == {3}
    t[0] = 30.0
    assert mon.failed_workers() == {0, 1, 2, 3}
    mon.revive(2)
    assert 2 not in mon.failed_workers()


def test_straggler_detector_feeds_balancer():
    det = StragglerDetector(4, ema=0.5, threshold=1.15)
    expected = np.ones(4)
    for _ in range(10):
        det.update(np.array([1.0, 1.0, 1.6, 1.0]))
    assert det.stragglers(expected) == [2]
    slow = det.slowdown(expected)
    assert slow[2] > 1.4 and slow[0] < 1.1
    # a straggler looks like imbalance: balancer moves layers off stage 2
    from repro.core.balancer import partition_balance, stage_loads
    layer_t = np.ones(16)
    lps = [4, 4, 4, 4]
    eff = layer_t.copy()
    eff[8:12] *= slow[2]      # stage 2's layers appear slower
    res = partition_balance(eff, 4)
    assert res.layers_per_stage[2] < 4


def test_heartbeat_rejects_unknown_worker():
    """A typo'd id must not silently grow the watch set (it could never be
    reported failed for the real worker); ``revive`` is the only way to
    (re-)register after construction."""
    t = [0.0]
    mon = HeartbeatMonitor(2, timeout_s=10.0, clock=lambda: t[0])
    with pytest.raises(KeyError):
        mon.beat(5)
    assert mon.known_workers() == {0, 1}
    mon.revive(5)                    # explicit registration
    mon.beat(5)
    assert mon.known_workers() == {0, 1, 5}
    # expire: deliberate departure (released worker) fails immediately …
    mon.expire(1)
    assert mon.failed_workers() == {1}
    mon.beat(1)                      # failed workers' beats are ignored
    assert mon.failed_workers() == {1}
    # … and revive is the recovery transition
    mon.revive(1)
    assert mon.failed_workers() == set()


def test_straggler_relative_slowdown_is_scale_free():
    det = StragglerDetector(4, ema=0.5)
    expected = np.array([1.0, 1.0, 1.0, 1.0])
    for _ in range(10):
        det.update(np.array([3.0, 3.0, 6.0, 3.0]))   # 3x scale error + 2x
    rel = det.relative_slowdown(expected)
    np.testing.assert_allclose(rel, [1.0, 1.0, 1.6, 1.0], atol=1e-6)
    # absolute slowdown would misread the calibration error as everyone
    # straggling
    assert det.slowdown(expected).min() >= 3.0
    det.reset(2)
    assert not det.initialized and len(det.times) == 2


def test_worker_pool_lifecycle():
    pool = WorkerPool(8)
    pool.release([6, 7])          # re-packing freed two workers
    assert pool.num_active == 6
    pool.fail(0)
    assert pool.num_active == 5
    granted = pool.request(2)
    assert granted == [6, 7]
    assert pool.num_active == 7
    assert pool.log[0] == "release:6"


def test_topk_compression_error_feedback():
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(1000), jnp.float32)
    vals, idx, residual = compress_topk(g, frac=0.1)
    rec = decompress_topk(vals, idx, g.shape)
    # top-k + residual reconstructs exactly
    np.testing.assert_allclose(np.asarray(rec + residual.reshape(-1)),
                               np.asarray(g), atol=1e-6)
    # picked entries are the largest-magnitude ones
    assert np.abs(np.asarray(vals)).min() >= np.abs(
        np.asarray(residual)).max() - 1e-6


def test_int8_quantization_bound():
    rng = np.random.RandomState(1)
    g = jnp.asarray(rng.randn(4096), jnp.float32)
    q, scale = int8_quantize(g)
    rec = int8_dequantize(q, scale)
    err = np.abs(np.asarray(rec) - np.asarray(g)).max()
    assert err <= float(scale) * 0.5 + 1e-6
    assert q.dtype == jnp.int8


def test_compressed_psum_single_axis():
    """psum over a singleton axis == identity recovery (exactness check of
    the codec inside the collective wrapper)."""
    from repro.runtime.compression import compressed_psum
    from repro.launch.mesh import _auto_mesh
    mesh = _auto_mesh((1,), ("d",))
    g = jnp.asarray(np.random.RandomState(2).randn(256), jnp.float32)

    def f(x):
        red, err = compressed_psum(x, "d", method="int8")
        return red, err

    from repro.pipeline.pipeline import _shard_map
    red, err = jax.jit(_shard_map(
        f, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec()))(g)
    np.testing.assert_allclose(np.asarray(red + err), np.asarray(g),
                               atol=1e-5)
