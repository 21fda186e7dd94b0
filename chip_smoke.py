"""Chip smoke: drive the Ditto cache's main path once on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded pool on a four-chip host

One chip.  A YCSB deployment (the setup ``workloads/gen.py`` cites):
10M keys, zipfian 0.99, a pool of 2**20 buckets x 8 slots holding 2**22
objects (42% of the key space), 256 client lanes.  A load phase SETs
2 x capacity distinct keys, so the pool is full and evicting; YCSB-A and
YCSB-C then run 2**20 operations each.  Everything goes through
``repro.core.execute()``.  Then one trace runs on a pool at the fused
backend's VMEM cap (loaded full first) on both backends (bit-equal hits,
OpStats and integer state required), and the same trace on this
process's CPU backend with the reference engine.

Four chips.  ``Cluster.make`` at 4x the one-chip pool, one one-chip pool
per chip: pipelined ``Cluster.execute`` must be bit-equal to per-step
routing on the same mesh; then a failure/recovery leg with hot-bucket
replication must keep ``gets + sets + route_drops == issued``.

Every check that fails exits non-zero.  Times printed here are those of a
smoke, not a benchmark.  The last line of standard output is the JSON
result; nothing is printed there when no TPU is found.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

SEED = 0
N_KEYS = 10_000_000


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"  check ok: {what}")


def _pool_bytes(state) -> int:
    import jax
    return int(sum(x.nbytes for x in jax.tree.leaves(state)))


def _live_bytes(state) -> int:
    size = np.asarray(state.size)
    return int(size[(size != 0) & (size != 255)].sum(dtype=np.int64))


def _check_pool(res, cfg, issued: int, what: str) -> None:
    """The pool invariants after a phase: occupancy within capacity,
    byte occupancy equal to the live slots' sizes recomputed from the
    table, and every issued operation counted.

    A single-tenant pool may overshoot its capacity transiently (DESIGN.md
    §8): concurrent evictors that pick the same victim free it once, and
    the next step's quota catches up.  So occupancy is held to capacity
    plus one step's lanes, and the overshoot is printed."""
    st, stats = res.state, res.stats
    n_cached = int(st.n_cached)
    lanes = res.clients.fc_slot.shape[0]
    check(n_cached <= cfg.capacity + lanes,
          f"{what}: n_cached {n_cached} <= capacity {cfg.capacity} + "
          f"{lanes} lanes (overshoot {max(0, n_cached - cfg.capacity)})")
    check(int(st.bytes_cached) == _live_bytes(st),
          f"{what}: bytes_cached {int(st.bytes_cached)} == sum of live "
          "slot sizes")
    done = int(stats.gets) + int(stats.sets)
    check(done == issued, f"{what}: gets + sets {done} == issued {issued}")


def _phase(name, cache, keys, writes, exec_cfg=None, **plan):
    """One execute() call (the ExecConfig's own plan unless ``plan=`` is
    given); prints its counters and times."""
    from repro.core import execute
    from repro.core.types import stats_delta
    t0 = time.perf_counter()
    res = execute(cache, keys, is_write=writes, exec_cfg=exec_cfg, **plan)
    total = time.perf_counter() - t0
    d = stats_delta(res.stats, cache.stats)
    ops = int(d.gets) + int(d.sets)
    hr = int(d.hits) / max(ops, 1)
    first = sum(w["wall_s"] for w in res.windows if w["compiles"])
    widths = sorted({w["width"] for w in res.windows})
    log(f"{name}: {ops} ops, hit rate {hr:.6f}, evictions "
        f"{int(d.evictions)}, n_cached {int(res.state.n_cached)}, "
        f"segments {len(res.windows)} widths {widths}; smoke, not a "
        f"benchmark: {total:.3f} s in all, plan {res.plan_s:.3f} s, "
        f"first calls (compile + run) {first:.3f} s")
    return res, hr, d


def one_chip(sz: dict, on_tpu: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import CacheConfig, ExecConfig, make
    from repro.core.execute import Cache, _runner
    from repro.core.types import init_clients, init_stats, merge_exec_config
    from repro.kernels.ops import FUSED_MAX_SLOTS
    from repro.workloads import interleave, ycsb

    out = {}
    # --- full-size deployment ---------------------------------------
    cfg = CacheConfig(n_buckets=sz["n_buckets"], assoc=8,
                      capacity=sz["capacity"], experts=("lru", "lfu"),
                      value_words=2)
    lanes = sz["lanes"]
    cache = make(cfg, lanes, seed=SEED)
    log(f"pool: {cfg.n_buckets} buckets x {cfg.assoc} slots = "
        f"{cfg.n_slots} slots, capacity {cfg.capacity} objects, "
        f"{_pool_bytes(cache.state)} bytes of state, {lanes} lanes")
    log(f"reduced: 8-byte payloads (value_words=2) instead of YCSB's "
        f"1 KB records; load phase {2 * cfg.capacity} SETs, executed "
        f"sequentially (plan=None) in {sz['load_chunks']} equal calls")

    rng = np.random.default_rng(SEED)
    load = (rng.permutation(sz["n_keys"])[:2 * cfg.capacity] + 1).astype(
        np.uint32).reshape(-1, lanes)
    for i, part in enumerate(np.split(load, sz["load_chunks"])):
        res, _, _ = _phase(f"load {i + 1}/{sz['load_chunks']}", cache,
                           part, np.ones(part.shape, bool), plan=None)
        cache = res.cache
    _check_pool(res, cfg, load.size, "load")
    check(int(res.stats.evictions) > 0 and int(res.state.n_cached)
          >= cfg.capacity * 0.95,
          f"load: pool full and evicting (n_cached "
          f"{int(res.state.n_cached)}, evictions "
          f"{int(res.stats.evictions)})")
    issued = load.size
    for i, w in enumerate(("A", "C")):
        keys, wr = ycsb(w, sz["run_ops"], n_keys=sz["n_keys"],
                        seed=SEED + 4 + i)
        res, hr, _ = _phase(f"YCSB-{w}", cache, interleave(keys, lanes),
                            interleave(wr, lanes))
        cache = res.cache
        issued += keys.size
        _check_pool(res, cfg, issued, f"YCSB-{w}")
        out[f"ycsb_{w.lower()}_hit_rate"] = hr

    # --- the same trace at the fused cap: fused, reference, CPU --------
    # Sampled eviction needs a dense table (capacity = n_slots / 2, as
    # in the deployment above), so the cap pool is loaded full first and
    # every run of the trace starts from a copy of that pool, with fresh
    # client lanes and counters.
    cap_cfg = CacheConfig(n_buckets=sz["cap_slots"] // 8, assoc=8,
                          capacity=sz["cap_slots"] // 4,
                          experts=("lru", "lfu"))
    n_load = cap_cfg.capacity * 5 // 4
    load = (rng.permutation(sz["n_keys"])[:n_load] + 1).astype(
        np.uint32).reshape(-1, lanes)
    res, _, _ = _phase("cap load", make(cap_cfg, lanes, seed=SEED), load,
                       np.ones(load.shape, bool), plan=None)
    check(int(res.stats.evictions) > 0, "cap load: pool full and evicting")
    pool = res.state
    cap_lanes = sz["cap_lanes"]

    def from_pool(state):
        return Cache(cap_cfg, state, init_clients(cap_cfg, cap_lanes, SEED),
                     init_stats())

    keys, wr = ycsb("A", sz["cap_ops"], n_keys=sz["n_keys"], seed=SEED + 2)
    keys, wr = interleave(keys, cap_lanes), interleave(wr, cap_lanes)
    log(f"cap trace: {cap_cfg.n_slots} slots (fused cap "
        f"{FUSED_MAX_SLOTS}), capacity {cap_cfg.capacity}, "
        f"{keys.size} YCSB-A ops on {cap_lanes} lanes, plan=None")
    fused_x = ExecConfig(backend="fused")
    if on_tpu:
        c0 = from_pool(pool)
        fn = _runner(merge_exec_config(cap_cfg, fused_x), False, True, None)
        text = fn.lower(c0.state, c0.clients, c0.stats,
                        jnp.asarray(keys), jnp.asarray(wr),
                        jnp.ones(keys.shape, jnp.uint32),
                        jnp.zeros(keys.shape, jnp.uint32)).compile().as_text()
        check("tpu_custom_call" in text,
              "the fused program's HLO holds tpu_custom_call")
    runs = {}
    for name, x in (("fused", fused_x), ("reference", ExecConfig())):
        res, hr, d = _phase(f"cap {name}",
                            from_pool(jax.tree.map(jnp.copy, pool)),
                            keys, wr, exec_cfg=x, plan=None)
        runs[name] = (res, d)
    (rf, df), (rr, dr) = runs["fused"], runs["reference"]
    check(np.array_equal(rf.hits, rr.hits), "cap: fused hits == reference")
    check(all(int(a) == int(b) for a, b in zip(df, dr)),
          "cap: fused OpStats == reference")
    for f in ("key", "size", "ptr", "insert_ts", "last_ts", "freq",
              "n_cached", "bytes_cached", "hist_ctr", "clock"):
        check(np.array_equal(np.asarray(getattr(rf.state, f)),
                             np.asarray(getattr(rr.state, f))),
              f"cap: fused state.{f} == reference")
    check(int(dr.evictions) > 0, f"cap: evicting ({int(dr.evictions)})")
    _check_pool(rr, cap_cfg, int((keys != 0).sum()), "cap reference")
    out["cap_hit_rate"] = int(dr.hits) / max(int(dr.gets + dr.sets), 1)

    # The process's CPU backend, reference engine.  The expert weights
    # are updated through f32 exp/pow, which XLA:CPU and XLA:TPU may
    # round differently, so a flipped expert choice may move a few
    # victims: equality is reported, a hit-rate tolerance is required.
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        res_c, hr_c, dc = _phase("cap reference on CPU",
                                 from_pool(jax.device_put(pool, cpu)),
                                 keys, wr, exec_cfg=ExecConfig(donate=False),
                                 plan=None)
    exact = np.array_equal(res_c.hits, rr.hits) and all(
        int(a) == int(b) for a, b in zip(dc, dr))
    gap = abs(hr_c - out["cap_hit_rate"])
    log(f"cap: CPU reference {'equals' if exact else 'differs from'} the "
        f"chip's reference (hit rates {hr_c:.6f} vs "
        f"{out['cap_hit_rate']:.6f}, gap {gap:.6f}; tolerance 0.005)")
    check(gap <= 0.005, "cap: CPU and chip hit rates within 0.005")
    out["cpu_equal"] = bool(exact)
    return out


def four_chips(sz: dict) -> dict:
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import CacheConfig
    from repro.core.hashing import bucket_of, hash_key
    from repro.dm import Cluster
    from repro.dm.sharded_cache import _dm_access_impl
    from repro.workloads import interleave, ycsb

    S = 4
    cfg = CacheConfig(n_buckets=S * sz["n_buckets"], assoc=8,
                      capacity=S * sz["capacity"], experts=("lru", "lfu"))
    lanes = sz["lanes"] // S
    cl = Cluster.make(cfg, S, lanes, seed=SEED)
    log(f"cluster: {S} shards x {cl.local.n_buckets} buckets x "
        f"{cfg.assoc} slots, global capacity {cfg.capacity}, "
        f"{_pool_bytes(cl.state)} bytes of state, {S * lanes} lanes")
    devs = jax.devices()[:S]
    for name, x in zip(cl.state._fields, cl.state):
        homes = sorted(s.device.id for s in x.addressable_shards)
        if len(homes) != S or homes != sorted(d.id for d in devs):
            raise SmokeFailure(f"state.{name} spans devices {homes}")
        if any(s.data.shape[0] * S != x.shape[0]
               for s in x.addressable_shards):
            raise SmokeFailure(f"state.{name} is not split 1/{S} per chip")
    log(f"  check ok: every state array is split 1/{S} across {S} "
        "distinct chips")

    keys, wr = ycsb("A", sz["four_rounds"] * S * lanes, n_keys=sz["n_keys"],
                    seed=SEED + 3)
    keys, wr = interleave(keys, S * lanes), interleave(wr, S * lanes)
    t0 = time.perf_counter()
    piped, hits = cl.execute(jnp.asarray(keys), jnp.asarray(wr))
    hits = np.asarray(jax.block_until_ready(hits))
    log(f"pipelined Cluster.execute: {keys.size} ops; smoke, not a "
        f"benchmark: {time.perf_counter() - t0:.3f} s with compile")
    step = jax.jit(functools.partial(_dm_access_impl, cl.mesh, cl.local))
    member = cl.membership()
    dm, seq = cl.dm, []
    t0 = time.perf_counter()
    for t in range(keys.shape[0]):
        dm, h = step(dm, jnp.asarray(keys[t]), jnp.asarray(wr[t]),
                     member=member)
        seq.append(np.asarray(h))
    log(f"per-step routing: {keys.shape[0]} calls; smoke, not a "
        f"benchmark: {time.perf_counter() - t0:.3f} s with compile")
    check(np.array_equal(np.stack(seq), hits),
          "pipelined hits == per-step hits")
    for part in ("state", "clients", "stats"):
        same = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
                   zip(jax.tree.leaves(getattr(dm, part)),
                       jax.tree.leaves(getattr(piped.dm, part))))
        check(same, f"pipelined {part} == per-step {part}")
    hit_rate = float(hits.sum()) / max(int((keys != 0).sum()), 1)

    # Failure leg: replicate the hottest buckets, lose shard 1, detect,
    # recover with rewarm; every issued request is counted.
    gb = cfg.n_buckets
    bkt = np.asarray(bucket_of(hash_key(jnp.asarray(keys.ravel())), gb))
    cl = piped.elect_replicas(np.bincount(bkt, minlength=gb), n_hot=4096)
    before = cl.stats
    issued = 0
    legs = (("replicated", None), ("shard 1 lost", "fail"),
            ("detected", "mark"), ("recovered", "recover"))
    for i, (name, action) in enumerate(legs):
        if action == "fail":
            cl = cl.inject_failure(1)
        elif action == "mark":
            cl = cl.mark_failed(1)
        elif action == "recover":
            cl, rep = cl.recover(1)
            log(f"  rewarm moved {rep.drained_objects} objects")
        k, w = ycsb("A", sz["leg_rounds"] * S * lanes, n_keys=sz["n_keys"],
                    seed=SEED + 10 + i)
        k, w = interleave(k, S * lanes), interleave(w, S * lanes)
        cl, _ = cl.execute(jnp.asarray(k), jnp.asarray(w))
        issued += int((k != 0).sum())
        d = cl.stats
        log(f"leg {name}: so far route_drops "
            f"{int(d.route_drops - before.route_drops)}, replica_writes "
            f"{int(d.replica_writes - before.replica_writes)}")
    d = cl.stats
    done = int(d.gets + d.sets + d.route_drops) - int(
        before.gets + before.sets + before.route_drops)
    check(done == issued,
          f"failure leg: gets + sets + route_drops {done} == issued {issued}")
    check(int(d.route_drops) > int(before.route_drops),
          "failure leg: requests bounced off the lost shard were counted")
    return {"cluster_hit_rate": hit_rate}


FULL = dict(n_keys=N_KEYS, n_buckets=2**20, capacity=2**22, lanes=256,
            load_chunks=8, run_ops=2**20, cap_slots=None, cap_lanes=64,
            cap_ops=2**15, four_rounds=64, leg_rounds=32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    # The CPU comparison needs JAX's CPU backend beside the TPU one.
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    try:
        from repro.compile_cache import enable_compile_cache
        from repro.kernels.ops import FUSED_MAX_SLOTS
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package: {e}",
              file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this smoke runs only on the chip", file=sys.stderr)
        return 3
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs a host with "
              f"{args.chips} TPU devices, found {len(devs)}",
              file=sys.stderr)
        return 3
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.device_kind} x {len(devs)} ({dev.platform})")
    sz = dict(FULL, cap_slots=FUSED_MAX_SLOTS)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            out = four_chips(sz)
        else:
            out = one_chip(sz, on_tpu=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    log(f"results: {json.dumps(out)}")
    log(f"peak_bytes_in_use {peak}; smoke, not a benchmark: "
        f"{time.perf_counter() - t0:.3f} s in all")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
