"""DM request-router correctness: no silent drops, decorrelated padded
lanes, clamp-then-normalize weight sync.

In-process tests run the 1-shard mesh on the session's single device;
the multi-shard skew regression runs in a subprocess with a forced host
device count (same pattern as test_dm.py).
"""

import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CacheConfig
from repro.core.cache import apply_penalties
from repro.core.types import init_clients
from repro.dm import dm_access, dm_make
from repro.dm.sharded_cache import _merge_positions, _positions
from repro.workloads import zipfian

pytestmark = pytest.mark.fast

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_pad_clients_decorrelates_rng():
    """Every request position a shard executes runs on a lane of its own
    (DESIGN.md §14): no two positions share an rng stream — identical
    streams draw identical sample offsets / expert choices, correlating
    evictions across supposedly independent lanes — and no position
    runs on a copy of another's state."""
    cfg = CacheConfig(n_buckets=64, assoc=8, capacity=128)
    # two shards' worth of held lanes at one node: 2 sources x 4 lanes
    clients = init_clients(cfg, 8, seed=0)
    full = _positions(clients, 2, 4)
    np.testing.assert_array_equal(np.asarray(full.rng),
                                  np.asarray(clients.rng))
    # a narrower routing capacity runs lanes [0, q) of every source block
    part = _positions(clients, 2, 3)
    rng = np.asarray(part.rng)
    np.testing.assert_array_equal(
        rng, np.asarray(clients.rng)[[0, 1, 2, 4, 5, 6]])
    assert len({tuple(r) for r in rng.tolist()}) == 6
    # what the positions advanced lands on exactly those lanes
    moved = part._replace(fc_delta=part.fc_delta + 1)
    back = np.asarray(_merge_positions(clients, moved, 2, 3).fc_delta)
    np.testing.assert_array_equal(back[:, 0], [1, 1, 1, 0, 1, 1, 1, 0])


def test_apply_penalties_clamp_then_normalize():
    """Weights must sum to exactly 1 even when the clamp binds (the old
    DM order normalized first, leaving sum > 1 after clamping)."""
    w = jnp.array([0.5, 0.5], jnp.float32)
    pen = jnp.array([1000.0, 0.0], jnp.float32)
    out = np.asarray(apply_penalties(w, pen, 0.1))
    assert abs(out.sum() - 1.0) < 1e-6
    assert (out > 0).all() and out[0] < out[1]


def test_single_shard_router_no_drops_and_weights_sum():
    cfg = CacheConfig(n_buckets=256, assoc=8, capacity=512,
                      experts=("lru", "lfu"), sync_period=16)
    mesh, dm, local = dm_make(cfg, n_shards=1, lanes_per_shard=16)
    stepf = jax.jit(functools.partial(dm_access, mesh, local))
    keys = zipfian(16 * 60, 3000, seed=0).reshape(60, 16)
    for t in range(60):
        dm, _ = stepf(dm, jnp.asarray(keys[t]))
    st = jax.tree.map(np.asarray, dm.stats)
    assert int(st.route_drops.sum()) == 0
    assert int(st.gets.sum()) == 60 * 16          # every request executed
    assert abs(float(np.asarray(dm.state.weights).sum()) - 1.0) < 1e-5


def test_batched_router_matches_sequential_rounds():
    """Grouped dm_access ([G, lanes] request blocks per destination)
    must make the same decisions as routing the rounds one step at a
    time, in the commuting regime (strict bucket-disjoint plan,
    eviction-free, single expert, no FC combining)."""
    from repro.workloads.plan import plan_groups

    lanes, T, G = 16, 48, 8
    cfg = CacheConfig(n_buckets=256, assoc=8, capacity=1024,
                      experts=("lru",), use_fc=False)
    keys = zipfian(lanes * T, 600, seed=5).reshape(T, lanes)
    plan = plan_groups(keys, cfg.n_buckets, G, scope="strict")
    rounds, _, _ = plan.rounds()

    mesh, dm_a, local = dm_make(cfg, n_shards=1, lanes_per_shard=lanes)
    step = jax.jit(functools.partial(dm_access, mesh, local))
    hits_seq = []
    for t in range(rounds.shape[0]):
        dm_a, h = step(dm_a, jnp.asarray(rounds[t]))
        hits_seq.append(np.asarray(h))
    hits_seq = np.stack(hits_seq)

    mesh, dm_b, local = dm_make(cfg, n_shards=1, lanes_per_shard=lanes)
    gstep = jax.jit(functools.partial(dm_access, mesh, local))
    hits_bat = []
    for g in range(plan.n_groups):
        dm_b, h = gstep(dm_b, jnp.asarray(plan.keys[g]))
        hits_bat.append(np.asarray(h))         # [G, lanes]
    hits_bat = np.concatenate(hits_bat)

    np.testing.assert_array_equal(hits_seq, hits_bat)
    sa = jax.tree.map(np.asarray, dm_a.stats)
    sb = jax.tree.map(np.asarray, dm_b.stats)
    for f in sa._fields:
        np.testing.assert_array_equal(getattr(sa, f), getattr(sb, f),
                                      f"OpStats.{f}")
    np.testing.assert_array_equal(np.asarray(dm_a.state.key),
                                  np.asarray(dm_b.state.key))
    assert int(sa.gets.sum()) == int((rounds != 0).sum())


def run_sub(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=REPO, timeout=540)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    return out.stdout


@pytest.mark.slow
def test_batched_router_multi_shard_matches_sequential():
    """The grouped [G, S, q] axis-1 all_to_all exchange must route
    identically to round-at-a-time routing on a REAL 8-shard mesh
    (n_shards=1 makes the exchange an identity, so it cannot catch a
    transposition in the grouped packing)."""
    out = run_sub("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools
import jax, jax.numpy as jnp, numpy as np
from repro.core import CacheConfig
from repro.dm import dm_make, dm_access
from repro.workloads import zipfian
from repro.workloads.plan import plan_groups

lanes_per, S, T, G = 8, 8, 40, 8
lanes = lanes_per * S
cfg = CacheConfig(n_buckets=1024, assoc=8, capacity=4096,
                  experts=("lru",), use_fc=False)
keys = zipfian(lanes * T, 1500, seed=7).reshape(T, lanes)
plan = plan_groups(keys, cfg.n_buckets, G, scope="strict")
rounds, _, _ = plan.rounds()

mesh, dm_a, local = dm_make(cfg, n_shards=S, lanes_per_shard=lanes_per)
step = jax.jit(functools.partial(dm_access, mesh, local))
hs = []
for t in range(rounds.shape[0]):
    dm_a, h = step(dm_a, jnp.asarray(rounds[t]))
    hs.append(np.asarray(h))
hs = np.stack(hs)

mesh, dm_b, local = dm_make(cfg, n_shards=S, lanes_per_shard=lanes_per)
gstep = jax.jit(functools.partial(dm_access, mesh, local))
hb = []
for g in range(plan.n_groups):
    dm_b, h = gstep(dm_b, jnp.asarray(plan.keys[g]))
    hb.append(np.asarray(h))
hb = np.concatenate(hb)

np.testing.assert_array_equal(hs, hb)
sa = jax.tree.map(np.asarray, dm_a.stats)
sb = jax.tree.map(np.asarray, dm_b.stats)
for f in sa._fields:
    np.testing.assert_array_equal(getattr(sa, f), getattr(sb, f), f)
np.testing.assert_array_equal(np.asarray(dm_a.state.key),
                              np.asarray(dm_b.state.key))
print("OK", int(sa.gets.sum()))
""")
    assert "OK" in out


@pytest.mark.slow
def test_skew_zero_silent_loss():
    """Adversarial skew (every key owned by one shard): requests beyond
    the route capacity are counted, never silently lost, and the
    full-capacity router executes every single one."""
    out = run_sub("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools
import jax, jax.numpy as jnp, numpy as np
from repro.core import CacheConfig
from repro.core.hashing import bucket_of, hash_key
from repro.dm import dm_make, dm_access

cfg = CacheConfig(n_buckets=1024, assoc=8, capacity=2048,
                  experts=("lru", "lfu"))
mesh, dm0, local = dm_make(cfg, n_shards=8, lanes_per_shard=8)

# keys that ALL hash to pool shard 0 (the pathological hot shard)
cand = jnp.arange(1, 40_000, dtype=jnp.uint32)
owner = np.asarray(bucket_of(hash_key(cand), 1024)) // local.n_buckets
hot = np.asarray(cand)[owner == 0]
assert len(hot) >= 64
rng = np.random.default_rng(0)
steps = 25
keys = rng.choice(hot, (steps, 64)).astype(np.uint32)

# 1) default capacity: overflow is COUNTED (zero silent loss)
dm = dm0
stepf = jax.jit(functools.partial(dm_access, mesh, local))
for t in range(steps):
    dm, h = stepf(dm, jnp.asarray(keys[t]))
st = jax.tree.map(np.asarray, dm.stats)
executed = int(st.gets.sum())
dropped = int(st.route_drops.sum())
assert dropped > 0, "skew test must actually exercise overflow"
assert executed + dropped == steps * 64, (executed, dropped)

# 2) full-capacity router (route_factor=0): nothing can be dropped
dm = dm0
stepf_full = jax.jit(functools.partial(dm_access, mesh, local,
                                       route_factor=0))
for t in range(steps):
    dm, h = stepf_full(dm, jnp.asarray(keys[t]))
st = jax.tree.map(np.asarray, dm.stats)
assert int(st.route_drops.sum()) == 0
assert int(st.gets.sum()) == steps * 64
print("OK", executed, dropped)
""")
    assert "OK" in out


@pytest.mark.slow
def test_zipfian_default_capacity_no_drops():
    """Realistic zipfian skew fits in the default (4x fair share) route
    capacity: zero drops, hit ratios uncorrupted."""
    out = run_sub("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools
import jax, jax.numpy as jnp, numpy as np
from repro.core import CacheConfig
from repro.dm import dm_make, dm_access
from repro.workloads import zipfian

cfg = CacheConfig(n_buckets=1024, assoc=8, capacity=2048,
                  experts=("lru", "lfu"))
mesh, dm, local = dm_make(cfg, n_shards=8, lanes_per_shard=8)
stepf = jax.jit(functools.partial(dm_access, mesh, local))
keys = zipfian(64 * 80, 20000, seed=0).reshape(80, 64)
for t in range(80):
    dm, h = stepf(dm, jnp.asarray(keys[t]))
st = jax.tree.map(np.asarray, dm.stats)
assert int(st.route_drops.sum()) == 0, int(st.route_drops.sum())
assert int(st.gets.sum()) == 80 * 64
print("OK")
""")
    assert "OK" in out
