"""Figs. 1 & 13: elasticity timeline — Ditto vs sharded-monolithic Redis.

Redis rescale 32->64->32 one-core nodes under YCSB-C: resharding moves half
of 10M objects, delaying the throughput gain / resource reclamation by
minutes and dipping throughput during migration. Ditto adjusts compute and
memory independently and near-instantly.

The Ditto side is a LIVE scenario through the DM runtime
(`repro.elastic.scenario`): one grow->shrink timeline over a single cache
instance, with lanes 32->64->32 and capacity 8192->16384->4096 (the final
shrink reclaims below the starting budget so the drain is exercised even
in quick mode). Per-window
throughput comes from the measured OpStats counters, migration bytes are
measured from real state deltas (a key appearing on a shard it did not
occupy before — zero for both grow and shrink), and the shrink is drained
online to the new capacity in a bounded number of batched eviction rounds.

The failover rows (DESIGN.md §14) kill a shard mid-trace on a REAL
4-shard mesh (in-process on a four-device host; otherwise a child on
four forced CPU host devices) and measure the hit-rate dip depth and
time-to-recover with hot-bucket replication on vs off.  The replicated
arm must dip shallower and drop fewer requests than the control — the
read fan-out keeps serving a replicated bucket from its live secondary
through the whole detection gap; asserted here, and the recovery-window
``hit_rate`` field is gated against history by ``bench_compare``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from repro.baselines import CLUSTER, RedisModel
from repro.core import CacheConfig
from repro.elastic import run_scenario
from benchmarks.common import REPO_ROOT, emit
from repro.workloads import ycsb


# The child for a process with fewer than four devices: four CPU host
# devices, set before its first jax import.
_FAILOVER_CHILD = r'''
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from benchmarks.elasticity import _failover_scenarios
print("ROWS " + json.dumps(_failover_scenarios(sys.argv[1] == "quick")))
'''


def _failover_scenarios(quick):
    """Both kill-a-shard arms on a 4-shard mesh of this process's devices
    (replication on, then off).  Returns the two rows."""
    from repro.elastic import HealthMonitor
    from repro.workloads.gen import failover_trace
    S, lanes, window = 4, 8, 16
    T = 192 if quick else 384
    t_fail = (T // 3 // window) * window
    t_rec = (2 * T // 3 // window) * window
    cfg = CacheConfig(n_buckets=1024, assoc=8, capacity=4096,
                      experts=("lru", "lfu"))
    # 70% of requests on a 48-key zipf core homed entirely on the shard
    # we kill — the worst case for an unreplicated cluster, the case
    # hot-key replication exists for.
    trace = failover_trace(T, lanes, S, cfg.n_buckets, hot_shard=1,
                           hot_fraction=0.7, n_hot=48, n_keys=3000, seed=7)
    timeline = [(t_fail, ("fail_shard", 1)), (t_rec, ("recover_shard", 1))]

    rows = []
    for name, rep_hot in (("failover_replicated", 96),
                          ("failover_control", 0)):
        res = run_scenario(cfg, trace.ravel(), timeline, n_shards=S,
                           lanes_per_shard=lanes, horizon=T, window=window,
                           health=HealthMonitor(S), replicate_hot=rep_hot,
                           seed=7)
        ws = res.windows
        pre = float(np.mean([w["hit_rate"] for w in ws
                             if w["t1"] <= t_fail and w["t0"] >= window]))
        outage = [w for w in ws if w["t0"] >= t_fail and w["t1"] <= t_rec]
        dip = pre - min(w["hit_rate"] for w in outage)
        detect = next((w["t1"] for w in ws if not w["routed"][1]), t_rec)
        rerouted = [w for w in outage if w["t0"] >= detect]
        rec_hr = (float(np.mean([w["hit_rate"] for w in rerouted]))
                  if rerouted else 0.0)
        after = [w for w in ws if w["t0"] >= t_rec]
        recov = next((i for i, w in enumerate(after)
                      if w["hit_rate"] >= 0.9 * pre), len(after))
        rows.append(dict(name=name, us_per_call=0.0, hit_rate=rec_hr,
                         pre_fail_hit_rate=round(pre, 4),
                         dip_depth_pp=round(100 * dip, 2),
                         detect_windows=(detect - t_fail) // window,
                         recover_windows=recov,
                         route_drops=sum(w["route_drops"] for w in ws),
                         replica_writes=sum(w["replica_writes"] for w in ws),
                         n_replicated=max(w["n_replicated"] for w in ws)))
    return rows


def failover_rows(quick=False):
    """Kill-a-shard timeline on a real 4-shard mesh, replication vs
    control.  A process that holds four devices runs it in-process; any
    other starts a child on four CPU host devices (a child cannot share
    this process's accelerator).  Returns the two benchmark rows;
    asserts the replication win."""
    import jax
    if len(jax.devices()) >= 4:
        rows = _failover_scenarios(quick)
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [os.path.join(REPO_ROOT, "src"), REPO_ROOT]))
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, "-c", _FAILOVER_CHILD,
             "quick" if quick else "full"],
            capture_output=True, text=True, env=env, cwd=REPO_ROOT,
            timeout=900)
        assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
        payload = [ln for ln in out.stdout.splitlines()
                   if ln.startswith("ROWS ")]
        rows = json.loads(payload[-1][len("ROWS "):])
    rep, ctrl = rows
    assert rep["name"] == "failover_replicated"
    # The replication win, measured: the read fan-out serves replicated
    # hot buckets from the live secondary through the detection gap, so
    # the replicated arm must dip meaningfully shallower than the
    # control and bounce fewer requests off the dead shard.
    assert rep["dip_depth_pp"] < ctrl["dip_depth_pp"] - 2.0, \
        f"replication did not flatten the dip: {rep} vs {ctrl}"
    assert rep["route_drops"] < ctrl["route_drops"], \
        f"replication did not reduce bounced requests: {rep} vs {ctrl}"
    # Post-reroute recovery must be no worse than the control's (warm
    # promoted secondaries vs cold rendezvous targets).
    assert rep["hit_rate"] >= ctrl["hit_rate"] - 0.02, \
        f"replicated recovery-window hit rate regressed: {rep} vs {ctrl}"
    return rows


def run(quick=False, failover_only=False):
    if failover_only:
        return emit(failover_rows(quick), "elasticity")
    rows = []
    redis = RedisModel()
    horizon = 1200.0
    events = [(0.0, 32), (180.0, 64), (600.0, 32)]
    t, tput, billed = redis.timeline(events, horizon)

    grow_at = 180.0
    # time until throughput reaches the 64-node steady state
    target = redis.steady_throughput(64) * 0.999
    reached = t[(t > grow_at) & (tput >= target)]
    grow_delay = (reached[0] - grow_at) if len(reached) else np.inf
    shrink_at = 600.0
    reclaimed = t[(t > shrink_at) & (billed <= 32)]
    shrink_delay = (reclaimed[0] - shrink_at) if len(reclaimed) else np.inf
    dip = 1.0 - tput[(t > grow_at) & (t < grow_at + grow_delay)].min() / \
        redis.steady_throughput(32)
    rows.append(dict(name="redis_rescale", grow_delay_min=grow_delay / 60,
                     reclaim_delay_min=shrink_delay / 60,
                     tput_dip_pct=100 * dip,
                     migration_bytes=redis.migration_bytes(0.5),
                     paper_grow_min=5.3, paper_reclaim_min=5.6))

    # Ditto: one live grow->shrink timeline through the DM cache.
    # Keyspace >> shrink target so the reclamation actually drains.
    n = 20_000 if quick else 60_000
    keys, _ = ycsb("C", n, n_keys=20_000, seed=0)
    cfg = CacheConfig(n_buckets=4096, assoc=8, capacity=8192,
                      experts=("lru", "lfu"))
    lanes0 = 32
    T = n // lanes0               # steps at the initial width
    t1, t2 = T // 3, 2 * T // 3
    timeline = [(t1, ("set_lanes", 64)), (t1, ("set_capacity", 16384)),
                (t2, ("set_lanes", 32)), (t2, ("set_capacity", 4096))]
    res = run_scenario(cfg, keys, timeline, n_shards=1,
                       lanes_per_shard=lanes0, horizon=T,
                       window=max(T // 40, 1))

    tput_32 = res.phase(0, t1, "tput_mops")
    tput_64 = res.phase(t1, t2, "tput_mops")
    shrink_ev = [e for e in res.events
                 if e["event"] == "set_capacity" and e["t"] >= t2][0]
    mig_total = sum(e["report"]["migration_bytes"] for e in res.events)
    drained = shrink_ev["report"]["drained_objects"]
    # Transition cost in the cost model: the drain's CAS stream on the MN
    # RNIC (grow and lane changes are scalar/CN-local: free).
    delay_s = drained / CLUSTER.rnic_msg_rate
    cap_after = int(np.asarray(res.dm.state.n_cached).sum())
    rows.append(dict(name="ditto_rescale",
                     tput_32c_mops=float(tput_32.mean()),
                     tput_64c_mops=float(tput_64.mean()),
                     transition_delay_s=delay_s,
                     migration_bytes=mig_total,
                     shrink_drain_steps=shrink_ev["report"]["drain_steps"],
                     n_cached_after_shrink=cap_after,
                     paper_tput_32c=5.0, paper_tput_64c=8.5))
    assert mig_total == 0, "elastic resize must not move data across shards"
    assert shrink_ev["report"]["drain_steps"] >= 1, "shrink should drain"
    assert cap_after <= 4096 + 64, "shrink must drain to the new capacity"
    rows += failover_rows(quick)
    return emit(rows, "elasticity")


if __name__ == "__main__":
    run(quick="--quick" in sys.argv,
        failover_only="--failover-only" in sys.argv)
