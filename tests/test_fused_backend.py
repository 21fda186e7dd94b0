"""Fused (Pallas) backend vs reference backend: decision equivalence.

The `backend="fused"` execution engine of `core/cache.access` must make
*identical* decisions to the pure-jnp reference on seeded traces — same
hit masks, same victim slots (hence identical table state), same
OpStats. These tests drive [T, C] traces through both and compare
everything bit-for-bit (Pallas kernels run in interpret mode on CPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CacheConfig, access, make_cache, run_trace
from repro.workloads import interleave, ycsb, zipfian

pytestmark = pytest.mark.fast

U32 = jnp.uint32


def _run(cfg, keys2d, writes2d=None, n_clients=None, seed=3):
    n_clients = n_clients or keys2d.shape[1]
    st, cl, _ = make_cache(cfg, n_clients, seed)
    fn = jax.jit(lambda s, c, k, w: run_trace(cfg, s, c, k, w))
    w = (jnp.zeros(keys2d.shape, bool) if writes2d is None
         else jnp.asarray(writes2d))
    tr = fn(st, cl, jnp.asarray(keys2d), w)
    return jax.tree.map(np.asarray, tr)


def _assert_equivalent(a, b):
    np.testing.assert_array_equal(a.hits, b.hits, "per-step hit counts")
    np.testing.assert_array_equal(a.ops, b.ops)
    np.testing.assert_allclose(a.weights, b.weights, atol=0, rtol=0)
    for f in a.stats._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a.stats, f)), np.asarray(getattr(b.stats, f)),
            f"OpStats.{f}")
    for f in a.state._fields:
        va, vb = np.asarray(getattr(a.state, f)), np.asarray(getattr(b.state, f))
        if va.dtype.kind == "f":
            np.testing.assert_allclose(va, vb, atol=0, rtol=0,
                                       err_msg=f"CacheState.{f}")
        else:
            np.testing.assert_array_equal(va, vb, f"CacheState.{f}")
    for f in ("fc_slot", "fc_delta", "fc_ins", "local_weights"):
        np.testing.assert_allclose(np.asarray(getattr(a.clients, f)),
                                   np.asarray(getattr(b.clients, f)),
                                   atol=0, rtol=0, err_msg=f"ClientState.{f}")


def _pair(base_kw, keys2d, writes2d=None):
    cfg_r = CacheConfig(backend="reference", **base_kw)
    cfg_f = CacheConfig(backend="fused", **base_kw)
    return (_run(cfg_r, keys2d, writes2d), _run(cfg_f, keys2d, writes2d))


@pytest.mark.parametrize("workload", ["A", "C"])
def test_ycsb_trace_equivalence(workload):
    """Same hits, victims, stats and weights on a YCSB trace with
    evictions, SETs, history regrets and weight syncs."""
    C = 16
    keys, wr = ycsb(workload, 60 * C, n_keys=600, seed=0)
    kw = dict(n_buckets=128, assoc=8, capacity=256,
              experts=("lru", "lfu"), sync_period=20, fc_threshold=4)
    a, b = _pair(kw, interleave(keys, C), interleave(wr, C))
    _assert_equivalent(a, b)
    assert a.stats.evictions > 0         # the eviction kernel really ran
    assert a.stats.regrets > 0           # the history probe really matched


def test_equivalence_many_experts_odd_lanes():
    """4 kernel experts + a lane count that does not divide block_b."""
    C = 11
    keys = zipfian(50 * C, 400, seed=2)
    kw = dict(n_buckets=64, assoc=8, capacity=128,
              experts=("lru", "lfu", "fifo", "size"), sync_period=10)
    a, b = _pair(kw, interleave(keys, C))
    _assert_equivalent(a, b)
    assert a.stats.evictions > 0


def test_equivalence_catchup_quota():
    """Tiny capacity + wide batches force the over-capacity catch-up
    (quota > 1) path through the quota-extended eviction kernel."""
    C = 32
    keys = zipfian(40 * C, 2000, theta=0.6, seed=4)
    kw = dict(n_buckets=32, assoc=8, capacity=64,
              experts=("hyperbolic", "lfu"), sync_period=16)
    a, b = _pair(kw, interleave(keys, C))
    _assert_equivalent(a, b)
    assert a.stats.evictions > 0


def test_fused_rejects_unsupported_experts():
    cfg = CacheConfig(n_buckets=64, assoc=8, capacity=128,
                      experts=("lru", "lruk"), backend="fused")
    st, cl, sa = make_cache(cfg, 8)
    with pytest.raises(ValueError, match="fused"):
        access(cfg, st, cl, sa, jnp.arange(1, 9, dtype=U32))


def test_fused_rejects_pool_above_vmem_cap():
    """Above the cap the fused backend refuses with a ValueError naming
    it — never a compiler crash, never a quiet switch of engine."""
    from repro.kernels.ops import FUSED_MAX_SLOTS
    cfg = CacheConfig(n_buckets=2 * FUSED_MAX_SLOTS // 8, assoc=8,
                      capacity=1024, backend="fused")
    shapes = jax.eval_shape(lambda: make_cache(cfg, 8))
    with pytest.raises(ValueError, match=f"FUSED_MAX_SLOTS={FUSED_MAX_SLOTS}"):
        jax.eval_shape(lambda s, c, a: access(
            cfg, s, c, a, jnp.arange(1, 9, dtype=U32)), *shapes)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="backend"):
        CacheConfig(n_buckets=64, assoc=8, capacity=128, backend="mosaic")


def test_fused_set_get_roundtrip():
    """Payload round-trip through the fused path (values stay jnp but hit
    decisions come from the probe kernel)."""
    cfg = CacheConfig(n_buckets=256, assoc=8, capacity=512,
                      experts=("lru", "lfu"), backend="fused")
    st, cl, sa = make_cache(cfg, 8)
    keys = jnp.arange(1, 9, dtype=U32)
    vals = jnp.stack([keys * 3, keys * 7], axis=1).astype(U32)
    st, cl, sa, r = access(cfg, st, cl, sa, keys,
                           is_write=jnp.ones(8, bool), values=vals)
    assert not bool(r.hit.any())
    st, cl, sa, r = access(cfg, st, cl, sa, keys)
    assert bool(r.hit.all())
    np.testing.assert_array_equal(np.asarray(r.value), np.asarray(vals))
