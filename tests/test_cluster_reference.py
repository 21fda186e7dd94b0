"""A `dm.Cluster` served through `execute()` against the plain four-node
reference (`bench/reference_dm.py`), and the properties the four-chip
deployment rests on: every request position a shard executes carries
persistent client state, the routing maps stay on the chips between
membership changes, and construction builds each shard on its own
device (DESIGN.md §14).

Everything that needs real shards runs in a subprocess with four forced
host devices (the test_dm.py pattern).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import CacheConfig
from repro.dm import Cluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(REPO, "src"), REPO]))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=REPO, timeout=540)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    return out.stdout


_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import CacheConfig, execute
from repro.dm import Cluster
S = 4
"""


@pytest.mark.slow
@pytest.mark.parametrize("mix,seed", [("A", 11), ("B", 4294967311)])
def test_cluster_matches_four_node_reference(mix, seed):
    """Hits of every round, every column of every shard's table, the
    per-shard scalars, the global and lane weights and the counters of a
    seeded YCSB run equal the plain reference's, bit for bit (no
    replication, every shard serving)."""
    out = run_sub(_PRELUDE + f"""
from bench import reference as ref, reference_dm as rdm
from bench.gen import ycsb
from bench.loop import COLUMNS, SCALARS
L, seed = 4, {seed}
cfg = CacheConfig(n_buckets=256, assoc=8, capacity=1024,
                  experts=("lru", "lfu"), sync_period=20)
keys, wr = ycsb("{mix}", 700 * S * L, n_keys=2400, seed=seed % 1000)
keys, wr = keys.reshape(-1, S * L), wr.reshape(-1, S * L)
cl = Cluster.make(cfg, S, L, seed=seed)
hits = []
for t in range(0, keys.shape[0], 100):
    res = execute(cl, keys[t:t + 100], is_write=wr[t:t + 100], plan=None)
    cl = res.cache
    hits.append(res.hits)
hits = np.concatenate(hits)
pool = ref.Pool(n_buckets=256, assoc=8, capacity=1024, sync_period=20)
rc = rdm.Cluster(pool, S)
tb, ln, want_hits, counts = rdm.replay(
    rc, rdm.empty_tables(rc), rdm.new_lanes(rc, L, seed), keys, wr)
np.testing.assert_array_equal(hits, want_hits)
st = cl.dm.state
for c in COLUMNS:
    np.testing.assert_array_equal(np.asarray(getattr(st, c)),
                                  np.asarray(tb.column(c)).reshape(-1), c)
for c in SCALARS:
    np.testing.assert_array_equal(np.asarray(getattr(st, c)),
                                  np.asarray(getattr(tb, c)), c)
np.testing.assert_array_equal(np.asarray(st.weights), np.asarray(tb.weights))
np.testing.assert_array_equal(np.asarray(cl.dm.clients.local_weights),
                              np.asarray(ln.local_w).reshape(-1, 2))
stats = cl.stats
for k, v in counts.items():
    assert int(getattr(stats, k)) == v, (k, int(getattr(stats, k)), v)
assert counts["regrets"] > 0 and counts["evictions"] > 0
print("REFOK", int(hits.sum()), counts["weight_syncs"])
""")
    assert "REFOK" in out


@pytest.mark.slow
def test_remote_position_increments_reach_the_table():
    """One key read 60 times from the first lane of sources 1-3, whose
    requests a shard serves at positions >= lanes_per_shard: each hit
    reaches the table's frequency, less at most the FC cache's lag (the
    increments a lane holds below ``fc_threshold``).  A shard that ran
    those positions on copies of its own lanes ended at 1."""
    out = run_sub(_PRELUDE + """
from repro.core.hashing import bucket_of, hash_key
L = 4
cfg = CacheConfig(n_buckets=256, assoc=8, capacity=1024)
lb = cfg.n_buckets // S
for src in (1, 2, 3):
    cand = np.arange(1, 5000, dtype=np.uint32)
    owner = np.asarray(bucket_of(hash_key(jnp.asarray(cand)),
                                 cfg.n_buckets)) // lb
    key = cand[owner != src][0]
    o = int(owner[owner != src][0])
    keys = np.zeros((61, S * L), np.uint32)
    keys[:, src * L] = key
    wr = np.zeros_like(keys, bool)
    wr[0] = keys[0] != 0              # the first request inserts it
    cl = Cluster.make(cfg, S, L)
    res = execute(cl, keys, is_write=wr, plan=None)
    assert int(res.hits.sum()) == 60
    st = res.cache.dm.state
    slot = np.nonzero(np.asarray(st.key) == key)[0]
    assert slot.size == 1 and slot[0] // (cfg.n_slots // S) == o
    freq = int(np.asarray(st.freq)[slot[0]])
    lag = cfg.fc_threshold - 1
    assert 1 + 60 - lag <= freq <= 61, (src, freq)
    print("FREQ", src, freq)
print("FREQOK")
""")
    assert "FREQOK" in out


@pytest.mark.slow
def test_cluster_make_holds_one_shard_per_device():
    """The compiled construction of a four-shard cluster holds no more
    than one shard's bytes on any device, and each array's shards land
    one on every device."""
    out = run_sub(_PRELUDE + """
from repro.dm.sharded_cache import _dm_init_jit
cfg = CacheConfig(n_buckets=1024, assoc=8, capacity=4096, value_words=64)
mesh, local, build = _dm_init_jit(cfg, S, 8)
mem = build.lower().compile().memory_analysis()
total = sum(x.size * x.dtype.itemsize
            for x in jax.tree.leaves(jax.eval_shape(build)))
# one shard's bytes, give or take alignment (a whole pool is 4x that)
assert mem.output_size_in_bytes <= total // S + 2**16, (
    mem.output_size_in_bytes, total)
assert mem.temp_size_in_bytes <= total // (4 * S), mem.temp_size_in_bytes
cl = Cluster.make(cfg, S, 8)
for x in jax.tree.leaves(cl.dm):
    homes = sorted(s.device.id for s in x.addressable_shards)
    assert homes == sorted(d.id for d in jax.devices()), homes
    assert all(s.data.shape[0] * S == x.shape[0]
               for s in x.addressable_shards)
print("SHARDOK", mem.output_size_in_bytes, total)
""")
    assert "SHARDOK" in out


def test_steady_state_call_reuses_device_membership(monkeypatch):
    """The routing maps are made once per membership and stay on the
    chips: calls reuse them without recomputing, and each mutator of
    (alive, routed, replicas) makes them anew."""
    from repro.core.execute import execute
    cfg = CacheConfig(n_buckets=64, assoc=4, capacity=96)
    cl = Cluster.make(cfg, 1, 8)
    routes = cl.routes
    assert routes is not None
    for x in routes:
        assert x.committed and x.sharding.is_fully_replicated

    def never(self):
        raise AssertionError("membership recomputed in a steady call")
    made = Cluster.membership
    monkeypatch.setattr(Cluster, "membership", never)
    keys = np.random.default_rng(4).integers(1, 400, (6, 8)).astype(
        np.uint32)
    res = execute(cl, keys)
    res = execute(res.cache, keys)
    assert res.cache.routes is routes
    cl2, _ = res.cache.execute(keys)
    assert cl2.routes is routes
    monkeypatch.setattr(Cluster, "membership", made)
    rep = np.full(cfg.n_buckets, 1, np.int32)
    assert cl2.with_replicas(rep).routes is not routes
    assert cl2.mark_failed(0).routes is not routes


def test_cluster_make_names_indivisible_sizes():
    cfg = CacheConfig(n_buckets=66, assoc=8, capacity=200)
    with pytest.raises(ValueError, match="n_buckets=66"):
        Cluster.make(cfg, 4, 2)
    cfg = CacheConfig(n_buckets=64, assoc=8, capacity=202)
    with pytest.raises(ValueError, match="capacity=202"):
        Cluster.make(cfg, 4, 2)
