"""Multi-tenant cache partitioning (DESIGN.md §11).

Contracts under test:

  * single-tenant configs (``n_tenants=1``) keep their exact pre-tenant
    shapes and decisions — passing an (ignored) tenant array changes
    nothing bit-for-bit;
  * multi-tenant traces decide bit-identically on the reference path,
    the fused Pallas ranked-eviction kernel, and the kernel's ref
    oracle;
  * per-tenant byte budgets are a HARD invariant: never exceeded at any
    step, even under flash-crowd load;
  * per-tenant expert weights converge independently (each tenant to
    its own best-fit algorithm);
  * the elastic arbiter splits the global budget deterministically with
    guaranteed floors, and the DM/scenario paths thread tenant ids end
    to end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CacheConfig, make_cache
from repro.core.cache import run_trace, run_trace_grouped
from repro.elastic.controller import (TenantArbiter, TenantArbiterConfig,
                                      TenantWindow)
from repro.kernels import ops, ref
from repro.workloads import (lru_friendly, plan_groups, tenant_mix,
                             zipfian)

pytestmark = pytest.mark.fast

U32 = jnp.uint32


def _two_tenant_trace(T=150, C=8, n_keys=2000, theta=0.7, seeds=(1, 2)):
    """[T, C] trace: lanes [:C//2] tenant 0, [C//2:] tenant 1, disjoint
    key spaces."""
    h = C // 2
    k0 = zipfian(T * h, n_keys, theta=theta, seed=seeds[0])
    k1 = zipfian(T * h, n_keys, theta=theta, seed=seeds[1]) + np.uint32(1 << 20)
    keys = np.zeros((T, C), np.uint32)
    keys[:, :h] = k0.reshape(T, h)
    keys[:, h:] = k1.reshape(T, h)
    ten = np.zeros((T, C), np.uint32)
    ten[:, h:] = 1
    return keys, ten


def _run(cfg, keys, ten=None, seed=3):
    st, cl, _ = make_cache(cfg, keys.shape[1], seed)
    fn = jax.jit(lambda s, c, k, t: run_trace(cfg, s, c, k, tenant=t))
    t = jnp.zeros(keys.shape, U32) if ten is None else jnp.asarray(ten)
    return jax.tree.map(np.asarray, fn(st, cl, jnp.asarray(keys), t))


def _assert_tr_equal(a, b):
    np.testing.assert_array_equal(a.hits, b.hits)
    for f in a.state._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a.state, f)), np.asarray(getattr(b.state, f)),
            f"CacheState.{f}")
    for f in a.stats._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a.stats, f)), np.asarray(getattr(b.stats, f)),
            f"OpStats.{f}")


# ----------------------------------------------------------------------
# Config + single-tenant compatibility.
# ----------------------------------------------------------------------

def test_config_validation():
    with pytest.raises(ValueError, match="n_tenants"):
        CacheConfig(n_buckets=64, assoc=8, capacity=128, n_tenants=0)
    with pytest.raises(ValueError, match="tenant_budget_blocks"):
        CacheConfig(n_buckets=64, assoc=8, capacity=128, n_tenants=3,
                    tenant_budget_blocks=(64, 64))
    with pytest.raises(ValueError, match="positive"):
        CacheConfig(n_buckets=64, assoc=8, capacity=128, n_tenants=2,
                    tenant_budget_blocks=(128, 0))


def test_default_budgets_split_evenly():
    cfg = CacheConfig(n_buckets=128, assoc=8, capacity=250, n_tenants=3)
    assert cfg.tenant_budgets == (84, 83, 83)
    assert sum(cfg.tenant_budgets) == cfg.budget_blocks
    # explicit budgets may overcommit (global budget still rules)
    cfg = CacheConfig(n_buckets=128, assoc=8, capacity=250, n_tenants=2,
                      tenant_budget_blocks=(250, 250))
    assert cfg.tenant_budgets == (250, 250)


def test_single_tenant_shapes_unchanged():
    """n_tenants=1 keeps the classic [E]/[C, E] layouts every existing
    consumer depends on."""
    cfg = CacheConfig(n_buckets=64, assoc=8, capacity=128,
                      experts=("lru", "lfu"))
    st, cl, _ = make_cache(cfg, 4)
    assert st.weights.shape == (2,)
    assert cl.local_weights.shape == (4, 2)
    assert cl.penalty_cnt.shape == (4,)
    assert st.tenant_bytes.shape == (1,)
    cfg2 = CacheConfig(n_buckets=64, assoc=8, capacity=128, n_tenants=3,
                       experts=("lru", "lfu"))
    st2, cl2, _ = make_cache(cfg2, 4)
    assert st2.weights.shape == (3, 2)
    assert cl2.local_weights.shape == (4, 3, 2)
    assert cl2.penalty_cnt.shape == (4, 3)


def test_single_tenant_ignores_tenant_ids():
    """With n_tenants=1 a tenant array is ignored: identical run."""
    keys, ten = _two_tenant_trace(T=60)
    cfg = CacheConfig(n_buckets=128, assoc=8, capacity=256,
                      experts=("lru", "lfu"), sync_period=20)
    _assert_tr_equal(_run(cfg, keys, None), _run(cfg, keys, ten))


# ----------------------------------------------------------------------
# Backend bit-equality + the ref oracle on multi-tenant traces.
# ----------------------------------------------------------------------

def test_multi_tenant_backends_bit_equal():
    """Eviction-heavy 2-tenant trace (asymmetric budgets): reference and
    fused engines agree bit-for-bit on state, stats and weights."""
    keys, ten = _two_tenant_trace()
    base = dict(n_buckets=128, assoc=8, capacity=256, n_tenants=2,
                tenant_budget_blocks=(96, 48), experts=("lru", "lfu"),
                sync_period=20)
    a = _run(CacheConfig(backend="reference", **base), keys, ten)
    b = _run(CacheConfig(backend="fused", **base), keys, ten)
    _assert_tr_equal(a, b)
    np.testing.assert_allclose(a.weights, b.weights, atol=0, rtol=0)
    assert int(a.stats.evictions) > 0   # the scoped eviction really ran
    assert a.state.weights.shape == (2, 2)


def test_ranked_eviction_kernel_matches_ref_with_tenants():
    """The fused kernel == ref oracle with per-op quotas + tenant
    filters over randomized tables (seed sweep)."""
    W, K, B, C = 16, 5, 24, 256
    for seed in range(3):
        rng = np.random.default_rng(seed)
        size = np.zeros(C, np.uint32)
        live = rng.random(C) < 0.5
        size[live] = rng.integers(1, 9, live.sum())
        ins = rng.integers(0, 1000, C).astype(np.uint32)
        last = rng.integers(0, 1000, C).astype(np.uint32)
        freq = rng.integers(1, 50, C).astype(np.uint32)
        tenant = rng.integers(0, 3, C).astype(np.uint32)
        offs = rng.integers(0, C, B).astype(np.int32)
        choice = rng.integers(0, 2, B).astype(np.int32)
        must = rng.random(B) < 0.8
        quota = rng.integers(0, 12, B).astype(np.int32)
        tfilt = rng.integers(-1, 3, B).astype(np.int32)
        ts = rng.integers(1, 1000, B).astype(np.float32)
        args = (jnp.asarray(size), jnp.asarray(ins), jnp.asarray(last),
                jnp.asarray(freq), jnp.asarray(offs), jnp.asarray(choice),
                jnp.asarray(must), jnp.asarray(quota), jnp.asarray(ts))
        kw = dict(window=W, k=K, experts=("lru", "lfu"))
        v1, c1 = ops.ranked_eviction_op(
            *args, tenant=jnp.asarray(tenant), tfilt=jnp.asarray(tfilt),
            **kw)
        v2, c2 = ref.ranked_eviction_ref(
            *args, tenant=jnp.asarray(tenant), tfilt=jnp.asarray(tfilt),
            **kw)
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2), seed)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2), seed)
        # filtered ops only ever claim their own tenant's slots
        v = np.asarray(v1)
        for b in range(B):
            for s in v[b][v[b] >= 0]:
                if tfilt[b] >= 0:
                    assert tenant[s] == tfilt[b], (seed, b, s)


# ----------------------------------------------------------------------
# The hard budget invariant.
# ----------------------------------------------------------------------

def test_budgets_never_exceeded_under_flash_crowd():
    """Per-step per-tenant occupancy <= budget, through a flash-crowd
    stampede of big objects (the benchmarks/tenants.py invariant)."""
    keys, ten, sizes = tenant_mix(
        12 * 400, 12,
        (dict(kind="zipf", n_keys=1_000, theta=0.9, lanes=4),
         dict(kind="scan", hot_keys=800, scan_len=300, lanes=2),
         dict(kind="flash", hot_keys=2_000, max_blocks=8, lanes=6)),
        seed=5)
    cfg = CacheConfig(n_buckets=384, assoc=8, capacity=768, n_tenants=3,
                      experts=("lru", "lfu"), sample_window=128)
    st, cl, sa = make_cache(cfg, 12, 0)

    from repro.core.cache import access

    def step(carry, xs):
        st, cl, sa = carry
        k, tn, sz = xs
        st, cl, sa, _ = access(cfg, st, cl, sa, k, tenant=tn, obj_size=sz)
        return (st, cl, sa), st.tenant_bytes

    fn = jax.jit(lambda st, cl, sa, k, tn, sz: jax.lax.scan(
        step, (st, cl, sa), (k, tn, sz)))
    (st, _, sa), occ = fn(st, cl, sa, jnp.asarray(keys),
                          jnp.asarray(ten), jnp.asarray(sizes))
    occ = np.asarray(occ)
    budget = np.asarray(st.tenant_budget)
    assert (occ <= budget[None, :]).all(), (
        occ.max(axis=0), budget)
    assert int(sa.evictions) > 0
    # the invariant is exact: tenant_bytes == per-tenant live sums
    st = jax.tree.map(np.asarray, st)
    live = (st.size != 0) & (st.size != 0xFF)
    for t in range(3):
        assert int(st.tenant_bytes[t]) == int(
            st.size[live & (st.tenant == t)].sum())


def test_growing_sets_cannot_break_the_budget():
    """SET re-sizes charge their byte delta through the same gate as
    inserts: a tenant at budget cannot inflate resident objects past it
    (the refused grow keeps the old size AND old payload), and shrinking
    SETs free room within the same step."""
    C = 4
    cfg = CacheConfig(n_buckets=64, assoc=8, capacity=64, n_tenants=2,
                      tenant_budget_blocks=(32, 32),
                      experts=("lru", "lfu"), value_words=2)
    st, cl, sa = make_cache(cfg, C, 0)

    from repro.core.cache import access
    step = jax.jit(lambda s, c, a, k, w, z, t, v: access(
        cfg, s, c, a, k, is_write=w, obj_size=z, tenant=t, values=v))
    keys = jnp.arange(1, C + 1, dtype=U32)
    ten = jnp.zeros((C,), U32)
    w1 = jnp.ones((C,), bool)
    v1 = jnp.stack([keys, keys], axis=1).astype(U32)
    # fill tenant 0 to its budget: 4 objects x 8 blocks = 32
    st, cl, sa, _ = step(st, cl, sa, keys, w1, jnp.full((C,), 8, U32),
                         ten, v1)
    assert int(st.tenant_bytes[0]) == 32
    # grow every object 8 -> 16 blocks: all grows must be refused,
    # sizes AND payloads keep their old values
    v2 = jnp.stack([keys * 7, keys * 9], axis=1).astype(U32)
    st, cl, sa, r = step(st, cl, sa, keys, w1, jnp.full((C,), 16, U32),
                         ten, v2)
    assert bool(np.asarray(r.hit).all())
    assert int(st.tenant_bytes[0]) == 32
    assert (np.asarray(st.tenant_bytes)
            <= np.asarray(st.tenant_budget)).all()
    st_np = jax.tree.map(np.asarray, st)
    live = (st_np.size != 0) & (st_np.size != 0xFF)
    assert (st_np.size[live] == 8).all()
    got = {int(k): st_np.values[i].tolist()
           for i, k in enumerate(st_np.key) if live[i]}
    for i, k in enumerate(range(1, C + 1)):
        assert got[k] == np.asarray(v1)[i].tolist()   # old payload kept
    # shrink 8 -> 2 then grow one object within the freed room: allowed
    st, cl, sa, _ = step(st, cl, sa, keys, w1, jnp.full((C,), 2, U32),
                         ten, v1)
    assert int(st.tenant_bytes[0]) == 8
    st, cl, sa, _ = step(st, cl, sa, keys[:1].reshape(1).repeat(C) *
                         jnp.asarray([1, 0, 0, 0], U32), w1,
                         jnp.full((C,), 16, U32), ten, v2)
    assert int(st.tenant_bytes[0]) == 2 * 3 + 16      # one grew to 16
    assert (np.asarray(st.tenant_bytes)
            <= np.asarray(st.tenant_budget)).all()


def test_overcommitted_budgets_share_the_pool():
    """Budgets may overcommit (sum > capacity): tenants then share the
    slack under the global quota eviction, classic-style."""
    keys, ten = _two_tenant_trace(T=120, theta=0.6)
    cfg = CacheConfig(n_buckets=64, assoc=8, capacity=128, n_tenants=2,
                      tenant_budget_blocks=(128, 128),
                      experts=("lru", "lfu"))
    tr = _run(cfg, keys, ten)
    assert int(tr.stats.evictions) > 0
    # each tenant holds under ITS budget; the global pool stays near cap
    assert (tr.state.tenant_bytes <= 128).all()
    assert int(tr.state.bytes_cached) <= 128 + keys.shape[1]


# ----------------------------------------------------------------------
# Per-tenant adaptation.
# ----------------------------------------------------------------------

def test_per_tenant_weights_converge_independently():
    """Tenant 0 runs a cyclic loop over 4/3 of its budget — the
    LRU-pathological pattern (recency always evicts the key needed
    next), so its regrets penalize lru; tenant 1 runs a fresh
    sliding-window pattern where stale frequencies mislead lfu.  Each
    tenant's weight row must converge toward its OWN best expert —
    opposite directions in one shared pool (the per-tenant [T, E]
    adaptation of DESIGN.md §11)."""
    T, C, h = 600, 8, 4
    n = T * h
    loop_keys = 128 * 4 // 3          # 4/3 of tenant 0's 128-block budget
    k0 = (np.arange(n, dtype=np.uint32) % loop_keys) + 1
    k1 = lru_friendly(n, window=256, seed=1) + np.uint32(1 << 20)
    keys = np.zeros((T, C), np.uint32)
    keys[:, :h] = k0.reshape(T, h)
    keys[:, h:] = k1.reshape(T, h)
    ten = np.zeros((T, C), np.uint32)
    ten[:, h:] = 1
    cfg = CacheConfig(n_buckets=256, assoc=8, capacity=256, n_tenants=2,
                      experts=("lru", "lfu"), sync_period=10)
    tr = _run(cfg, keys, ten)
    w = np.asarray(tr.state.weights)           # [2, 2] cols: lru, lfu
    assert int(tr.stats.regrets) > 0
    assert w[0, 1] > w[0, 0], w  # loop tenant trusts lfu
    assert w[1, 0] > w[1, 1], w  # sliding-window tenant trusts lru


def test_grouped_multi_tenant_matches_sequential():
    """Strict bucket-disjoint plans stay exactly sequential with tenant
    ids threaded through the batched engine (eviction-free regime)."""
    keys, ten = _two_tenant_trace(T=60, n_keys=400, theta=0.99)
    cfg = CacheConfig(n_buckets=256, assoc=8, capacity=1024, n_tenants=2,
                      experts=("lru", "lfu"), use_fc=False)
    plan = plan_groups(keys, cfg.n_buckets, 8, scope="strict", tenants=ten)
    assert plan.tenants is not None
    rk, rw, _ = plan.rounds()
    rt = plan.tenants.reshape(-1, keys.shape[1])
    st, cl, _ = make_cache(cfg, keys.shape[1], 3)
    seq = jax.jit(lambda s, c, k, t: run_trace(cfg, s, c, k, tenant=t))(
        st, cl, jnp.asarray(rk), jnp.asarray(rt))
    bat = jax.jit(lambda s, c, k, t: run_trace_grouped(
        cfg, s, c, k, tenant=t))(
        st, cl, jnp.asarray(plan.keys), jnp.asarray(plan.tenants))
    _assert_tr_equal(jax.tree.map(np.asarray, seq),
                     jax.tree.map(np.asarray, bat))


# ----------------------------------------------------------------------
# Elastic arbitration + DM threading.
# ----------------------------------------------------------------------

def test_arbiter_floors_and_demand_split():
    arb = TenantArbiter(TenantArbiterConfig(floor_frac=0.5, ema=1.0))
    wins = [TenantWindow(occupancy_blocks=10, budget_blocks=100,
                         hit_rate=0.9, miss_blocks=0.0),
            TenantWindow(occupancy_blocks=100, budget_blocks=100,
                         hit_rate=0.4, miss_blocks=5000.0)]
    budgets = arb.propose(300, wins)
    assert budgets is not None
    assert sum(budgets) == 300
    floor = int((300 // 2) * 0.5)
    assert all(b >= floor for b in budgets)
    assert budgets[1] > budgets[0]       # demand earns budget
    # hysteresis: same demand against the new split -> no churn
    wins2 = [w._replace(budget_blocks=b) for w, b in zip(wins, budgets)]
    assert arb.propose(300, wins2) is None


def test_arbiter_idle_tenants_split_evenly():
    """All-idle demand re-centers an uneven split; an already-even one
    sits inside the hysteresis band (no churn)."""
    arb = TenantArbiter()
    uneven = [TenantWindow(0, 150, 0.0, 0.0), TenantWindow(0, 50, 0.0, 0.0)]
    budgets = arb.propose(200, uneven)
    assert budgets is not None and sum(budgets) == 200
    assert abs(budgets[0] - budgets[1]) <= 1
    even = [TenantWindow(0, 100, 0.0, 0.0), TenantWindow(0, 100, 0.0, 0.0)]
    assert TenantArbiter().propose(200, even) is None


def test_split_tenant_budgets_conserves_totals():
    """Per-shard budget shares sum EXACTLY to the global budgets — the
    hard invariant would silently inflate/deflate under floor division
    (e.g. budget 2 over 4 shards must enforce 2 globally, not 4)."""
    from repro.core.types import split_tenant_budgets
    for budgets, n_shards in (((2, 7, 100), 4), ((1, 1), 8), ((97,), 3)):
        m = split_tenant_budgets(budgets, n_shards)
        assert m.shape == (n_shards, len(budgets))
        np.testing.assert_array_equal(m.sum(axis=0), list(budgets))
        assert (m >= 0).all()


def test_dm_access_threads_tenants_single_shard():
    from repro.dm.sharded_cache import dm_access, dm_make
    cfg = CacheConfig(n_buckets=256, assoc=8, capacity=512, n_tenants=2,
                      experts=("lru", "lfu"))
    mesh, dm, local = dm_make(cfg, 1, 8)
    keys = jnp.arange(1, 9, dtype=U32)
    ten = jnp.asarray([0, 0, 0, 0, 1, 1, 1, 1], U32)
    sz = jnp.full((8,), 3, U32)
    dm, hits = dm_access(mesh, local, dm, keys, obj_size=sz, tenant=ten)
    assert not bool(np.asarray(hits).any())
    tb = np.asarray(dm.state.tenant_bytes).sum(axis=0)
    np.testing.assert_array_equal(tb, [12, 12])   # 4 inserts x 3 blocks
    dm, hits = dm_access(mesh, local, dm, keys, obj_size=sz, tenant=ten)
    assert bool(np.asarray(hits).all())


def test_scenario_reports_tenant_windows_and_arbitrates():
    from repro.elastic import run_scenario
    keys, ten, sizes = tenant_mix(
        8 * 240, 8,
        (dict(kind="zipf", n_keys=400, theta=1.0, lanes=4),
         dict(kind="flash", hot_keys=600, max_blocks=4, lanes=4)),
        seed=3)
    cfg = CacheConfig(n_buckets=256, assoc=8, capacity=384, n_tenants=2,
                      experts=("lru", "lfu"), sample_window=64)
    res = run_scenario(cfg, keys.reshape(-1), [], n_shards=1,
                       lanes_per_shard=8, horizon=240, window=40,
                       sizes=sizes.reshape(-1), tenants=ten.reshape(-1),
                       arbiter=TenantArbiter())
    w = res.windows[-1]
    assert len(w["tenant_blocks"]) == 2
    assert len(w["tenant_hit_rate"]) == 2
    assert sum(w["tenant_budget"]) == w["capacity"]
    assert all(b <= c for b, c in zip(w["tenant_blocks"],
                                      w["tenant_budget"]))
    assert any(e["event"] == "set_tenant_budgets" for e in res.events)
