"""L0 near-cache tier (DESIGN.md §15): disabled-path bit-identity,
read-your-writes / no-stale-reads coherence under concurrent lanes,
replication and shard failover, and the epoch flush.

Core-engine legs run in-process; the cluster legs (replication,
failover, drain) run on a real 4-shard mesh in a subprocess, per the
single-device test-session brief."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (CacheConfig, execute, init_cache, init_clients,
                        init_stats, make)
from repro.core.cache import access_group

U32 = jnp.uint32
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mixed_trace(T, C, n_keys, seed, write_frac=0.3):
    rng = np.random.default_rng(seed)
    keys = rng.zipf(1.2, size=(T, C)).clip(1, n_keys).astype(np.uint32)
    writes = rng.random((T, C)) < write_frac
    # Unique-per-write payloads: value word 0 encodes the key, word 1 a
    # globally unique write stamp, so any stale read is unambiguous.
    stamps = np.arange(T * C, dtype=np.uint32).reshape(T, C)
    vals = np.stack([keys, stamps], axis=-1)
    return (jnp.asarray(keys), jnp.asarray(writes),
            jnp.asarray(vals.astype(np.uint32)))


def _run_steps(cfg, keys, writes, vals):
    """Drive [T, C] rows through access_group one row at a time, checking
    the oracle invariant at every step:

    * no-stale-reads: a hit's value word 1 equals the stamp of the last
      write to that key COMMITTED IN A PRIOR STEP (step-entry snapshot
      semantics — exactly what the remote path serves);
    * read-your-writes: once a write commits, later steps that hit the
      key never see an older stamp.
    """
    state = init_cache(cfg)
    clients = init_clients(cfg, keys.shape[1])
    stats = init_stats()
    committed = {}          # key -> stamp of last committed write
    T, C = keys.shape
    for t in range(T):
        state, clients, stats, res = access_group(
            cfg, state, clients, stats, keys[t][None],
            is_write=writes[t][None], values=vals[t][None])
        hit = np.asarray(res.hit[0])
        val = np.asarray(res.value[0])
        for c in range(C):
            k = int(keys[t, c])
            if hit[c] and not bool(writes[t, c]) and k in committed:
                assert int(val[c, 0]) == k, f"t={t} lane={c}: wrong payload"
                assert int(val[c, 1]) == committed[k], (
                    f"t={t} lane={c} key={k}: stale read "
                    f"(got stamp {int(val[c, 1])}, committed {committed[k]})")
        # Commit this row's payload installs.  A write-HIT applies via
        # the SET scatter (last writer in lane order wins); a MISS — read
        # or write — goes through read-through insert dedup
        # (_first_winner: the FIRST missing lane per key installs ITS
        # payload, later duplicates drop).  All lanes of a key share the
        # snapshot, so they agree on hit/miss.
        first_ins = set()
        for c in range(C):
            k = int(keys[t, c])
            if hit[c]:
                if bool(writes[t, c]):
                    committed[k] = int(vals[t, c, 1])
            elif k not in first_ins:
                first_ins.add(k)
                committed[k] = int(vals[t, c, 1])
    return stats


@pytest.mark.fast
def test_l0_disabled_is_default_and_counts_zero():
    cfg = CacheConfig(n_buckets=128, assoc=4, capacity=128)
    assert cfg.l0_entries == 0
    keys, writes, vals = _mixed_trace(40, 4, 300, seed=0)
    stats = _run_steps(cfg, keys, writes, vals)
    assert int(stats.l0_hits) == 0
    assert int(stats.l0_invalidations) == 0


@pytest.mark.fast
@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_l0_zero_bit_identical_to_absent(backend):
    """l0_entries=0 must trace to the same decisions/stats as a config
    that never mentions the field (the pre-L0 path): identical configs
    hash equal, and the engine's l0 gate is static."""
    base = CacheConfig(n_buckets=128, assoc=4, capacity=128,
                       backend=backend)
    explicit = CacheConfig(n_buckets=128, assoc=4, capacity=128,
                           backend=backend, l0_entries=0)
    assert hash(base) == hash(explicit) and base == explicit
    keys, writes, vals = _mixed_trace(30, 4, 200, seed=1)
    sa = _run_steps(base, keys, writes, vals)
    sb = _run_steps(explicit, keys, writes, vals)
    for a, b in zip(sa, sb):
        assert int(a) == int(b)


@pytest.mark.fast
@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_l0_no_stale_reads_concurrent_lanes(backend):
    """Read-your-writes + no-stale-reads with the tier enabled: every hit
    (L0 or remote) serves the last committed write's stamp."""
    cfg = CacheConfig(n_buckets=128, assoc=4, capacity=128,
                      backend=backend, l0_entries=6)
    keys, writes, vals = _mixed_trace(80, 4, 60, seed=2, write_frac=0.35)
    stats = _run_steps(cfg, keys, writes, vals)
    # The hot trace must actually exercise the tier, or the test is vacuous.
    assert int(stats.l0_hits) > 0
    assert int(stats.l0_invalidations) > 0


@pytest.mark.fast
def test_l0_reference_fused_decision_equal():
    """The L0 probe/fill is shared jnp code outside the Pallas kernels:
    reference and fused backends must produce bit-equal state and stats
    with the tier enabled."""
    keys, writes, vals = _mixed_trace(40, 4, 100, seed=3)
    outs = {}
    for backend in ("reference", "fused"):
        cfg = CacheConfig(n_buckets=128, assoc=4, capacity=128,
                          backend=backend, l0_entries=6)
        state, clients, stats = (init_cache(cfg), init_clients(cfg, 4),
                                 init_stats())
        for t in range(keys.shape[0]):
            state, clients, stats, _ = access_group(
                cfg, state, clients, stats, keys[t][None],
                is_write=writes[t][None], values=vals[t][None])
        outs[backend] = (state, clients, stats)
    sa, sb = outs["reference"][0], outs["fused"][0]
    for name in sa._fields:
        a, b = np.asarray(getattr(sa, name)), np.asarray(getattr(sb, name))
        if name == "ext":
            # f32 extension metadata carries a pre-existing ulp-level
            # backend difference (decision-equivalence, not bit-equality,
            # is the repo's fused contract for it) — L0 must not widen it.
            np.testing.assert_allclose(a, b, atol=1e-5)
        else:
            np.testing.assert_array_equal(a, b, err_msg=f"state.{name}")
    for tree_a, tree_b in ((outs["reference"][1], outs["fused"][1]),
                           (outs["reference"][2], outs["fused"][2])):
        for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.fast
def test_l0_hits_cost_zero_rdma():
    """An L0 hit adds to gets/hits/hit_bytes but to NO rdma op/byte
    counter — repeating a read-only row must leave the wire counters at
    exactly the first pass's totals once every lane's key is resident."""
    cfg = CacheConfig(n_buckets=64, assoc=4, capacity=64, l0_entries=4)
    state, clients, stats = init_cache(cfg), init_clients(cfg, 4), init_stats()
    row = jnp.asarray([[1, 2, 3, 4]], U32)
    # install + one read pass (the read pass fills L0)
    state, clients, stats, _ = access_group(
        cfg, state, clients, stats, row, is_write=jnp.ones((1, 4), bool))
    state, clients, stats, _ = access_group(cfg, state, clients, stats, row)
    base = {f: int(getattr(stats, f)) for f in
            ("rdma_read", "rdma_write", "rdma_cas", "rdma_faa",
             "rdma_read_bytes", "rdma_write_bytes")}
    for _ in range(5):
        state, clients, stats, res = access_group(cfg, state, clients,
                                                  stats, row)
        assert bool(jnp.all(res.hit))
    for f, v in base.items():
        assert int(getattr(stats, f)) == v, f"{f} grew on pure L0 hits"
    assert int(stats.l0_hits) == 20
    assert int(stats.gets) == 24 and int(stats.hits) == 24


@pytest.mark.fast
def test_l0_through_execute_api():
    """The tier threads through the public execute() surface untouched
    and pays for itself in wire bytes on a zipfian read trace."""
    trace = jnp.asarray(np.random.default_rng(3).zipf(1.5, size=4096).clip(
        1, 500).astype(np.uint32).reshape(512, 8))
    base = execute(make(CacheConfig(n_buckets=256, assoc=4, capacity=256),
                        n_clients=8), trace)
    l0 = execute(make(CacheConfig(n_buckets=256, assoc=4, capacity=256,
                                  l0_entries=8), n_clients=8), trace)
    assert int(base.stats.l0_hits) == 0
    assert int(l0.stats.l0_hits) > 0
    assert int(l0.stats.rdma_read_bytes) < int(base.stats.rdma_read_bytes)
    hr_base = int(base.stats.hits) / int(base.stats.gets)
    hr_l0 = int(l0.stats.hits) / int(l0.stats.gets)
    assert abs(hr_base - hr_l0) < 0.01      # within 1pp


# ---------------------------------------------------------------------
# Real 4-shard mesh: coherence under replication + failover, epoch
# flush on drain (subprocess; slow lane — the session sees one device).
# ---------------------------------------------------------------------

def run_sub(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=REPO, timeout=540)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    return out.stdout


_SUB_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import CacheConfig
from repro.dm import Cluster

def assert_l0_coherent(cluster):
    # THE no-stale invariant against ground truth: every L0 entry the
    # probe would treat as valid (epoch current + token == its bucket's
    # version) must name a key live in that shard's bucket with exactly
    # the cached payload and size — an L0 hit always returns
    # byte-for-byte what the remote path would have.
    st, cl = cluster.dm.state, cluster.dm.clients
    S, lanes = cluster.n_shards, cluster.lanes_per_shard
    lb, A = cluster.local.n_buckets, cluster.local.assoc
    key, size = np.asarray(st.key), np.asarray(st.size)
    values, bver = np.asarray(st.values), np.asarray(st.bucket_ver)
    epoch = np.asarray(st.l0_epoch)
    l0_key, l0_bkt = np.asarray(cl.l0_key), np.asarray(cl.l0_bkt)
    l0_tok, l0_sz = np.asarray(cl.l0_tok), np.asarray(cl.l0_sz)
    l0_val, seen = np.asarray(cl.l0_val), np.asarray(cl.l0_seen_epoch)
    checked = 0
    held = l0_key.shape[0] // S     # every shard holds S * lanes lanes
    for lane in range(l0_key.shape[0]):
        s = lane // held
        if seen[lane] != epoch[s]:
            continue            # whole lane flushes at its next step
        for e in range(l0_key.shape[1]):
            k = int(l0_key[lane, e])
            if k == 0:
                continue
            gb = s * lb + int(l0_bkt[lane, e])
            if int(l0_tok[lane, e]) != int(bver[gb]):
                continue        # self-invalidates at the next probe
            sl = slice(gb * A, (gb + 1) * A)
            hitm = (key[sl] == k) & (size[sl] != 0) & (size[sl] != 0xFF)
            assert hitm.sum() == 1, (lane, k, gb)
            slot = gb * A + int(np.nonzero(hitm)[0][0])
            assert (l0_val[lane, e] == values[slot]).all(), (lane, k)
            assert int(l0_sz[lane, e]) == int(size[slot])
            checked += 1
    return checked

def chunk(n, L, seed):
    r = np.random.default_rng(seed)
    keys = r.zipf(1.15, size=(n, L)).clip(1, 1500).astype(np.uint32)
    writes = r.random((n, L)) < 0.3
    return jnp.asarray(keys), jnp.asarray(writes)
"""


@pytest.mark.slow
def test_l0_coherent_under_replication_and_failover():
    """Every valid L0 entry equals the owning shard's table — through
    writes, hot-bucket replication (mirrors bump the secondary's bucket
    versions via the sideband write path), a mid-trace shard failure and
    the rewarming recovery; epoch bumps flush at each out-of-band step."""
    out = run_sub(_SUB_PRELUDE + """
cfg = CacheConfig(n_buckets=256, assoc=8, capacity=512, l0_entries=8)
cl = Cluster.make(cfg, n_shards=4, lanes_per_shard=8)
keys, writes = chunk(30, 32, 1)
cl, _ = cl.execute(keys, is_write=writes)
assert assert_l0_coherent(cl) > 0
assert int(cl.stats.l0_hits) > 0

loads = np.zeros(cfg.n_buckets); loads[:64] = 1.0
cl = cl.elect_replicas(loads, 64)
keys, writes = chunk(30, 32, 2)
cl, _ = cl.execute(keys, is_write=writes)
assert_l0_coherent(cl)

ep0 = np.asarray(cl.dm.state.l0_epoch).copy()
cl = cl.inject_failure(2).mark_failed(2)
assert (np.asarray(cl.dm.state.l0_epoch) == ep0 + 1).all()
keys, writes = chunk(20, 32, 3)
cl, _ = cl.execute(keys, is_write=writes)
assert_l0_coherent(cl)

cl, rep = cl.recover(2)
if rep.drained_objects:
    assert (np.asarray(cl.dm.state.l0_epoch) >= ep0 + 2).all()
keys, writes = chunk(20, 32, 4)
inval0 = int(cl.stats.l0_invalidations)
cl, _ = cl.execute(keys, is_write=writes)
assert_l0_coherent(cl)
assert int(cl.stats.l0_invalidations) > inval0
print("OK", int(cl.stats.l0_hits))
""")
    assert "OK" in out


@pytest.mark.slow
def test_l0_epoch_flush_on_shrink_drain():
    """A shrink drain evicts outside access_group, so it must advance
    the epoch and drop every lane's near-cache contents."""
    out = run_sub(_SUB_PRELUDE + """
cfg = CacheConfig(n_buckets=256, assoc=8, capacity=1024,
                  capacity_blocks=1024, l0_entries=8)
cl = Cluster.make(cfg, n_shards=4, lanes_per_shard=8)
keys = jnp.asarray(np.random.default_rng(0).integers(
    1, 800, size=(40, 32)).astype(np.uint32))
cl, _ = cl.execute(keys)
assert np.count_nonzero(np.asarray(cl.dm.clients.l0_key)) > 0
ep0 = np.asarray(cl.dm.state.l0_epoch).copy()
cl, rep = cl.drain_to(256)
assert rep.drained_objects > 0
assert (np.asarray(cl.dm.state.l0_epoch) > ep0).any()
assert_l0_coherent(cl)
print("OK")
""")
    assert "OK" in out
