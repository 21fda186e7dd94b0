"""Ditto memory nodes, one a chip, behind the client-side router: a
``repro.dm.Cluster`` served through ``repro.core.execute``.

Set-up builds the cluster with the program's ``Cluster.make`` at the fill
width (every shard on its own chip), fills it with ``execute`` calls of
SETs, gives every slot the record of its key (``bench/records.py``,
each shard's on its own chip), and hands the filled pool to the cell's
client lanes with fresh lane state and counters, as a restarted client
tier would.  Then ``bench/loop.py`` serves the warm-up and the window.
After the window the per-shard counters give ``router.load_imbalance``,
the program's account is read back and freed, and
``bench/reference_dm.py`` replays the same fill and requests from the
seed.  Scalars and weights compare per shard.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import loop
from bench import records
from bench import reference as ref
from bench import reference_dm as rdm
from bench import stages
from bench import traffic as traffic_mod
# The configuration's pool is the GLOBAL one, read as the one-node system
# reads its own.
from bench.systems.core import pool_of, program_config  # noqa: F401


def traffic(job):
    """The run's fill and trace, the seeds of the two sets of lanes and the
    salt of the records."""
    tr = traffic_mod.build(job.traffic, nodes=job.config["n_shards"],
                           capacity=job.config["pool"]["capacity"],
                           lanes=job.config["lanes"],
                           fill_lanes=job.config["fill_lanes"],
                           seconds=job.seconds, seed=job.seed)
    fill_seed, lane_seed, salt = traffic_mod.seeds(job.seed, 5)[2:]
    return tr, (fill_seed, lane_seed), salt


def _program(config: dict):
    """The program's cluster entry points; a program that cannot build a
    cluster shard by shard (one that predates ``cluster_runner_hlo``)
    cannot hold this configuration's pool and is refused at once."""
    try:
        from repro.core.execute import cluster_runner_hlo  # noqa: F401
    except ImportError as e:
        raise RuntimeError(
            "this program has no sharded cluster construction "
            "(repro.core.execute.cluster_runner_hlo): it cannot hold the "
            f"{config['name']} pool") from e
    from repro.core.types import ExecConfig
    if ExecConfig().route_factor != config["route_factor"]:
        raise RuntimeError(
            f"the program routes at route_factor "
            f"{ExecConfig().route_factor}, the configuration states "
            f"{config['route_factor']}")


def fresh_lanes(cluster, cfg, lanes_per_shard: int, seed: int):
    """``cluster`` with new client lanes and zeroed counters: every shard
    holds ``lanes_per_shard`` lanes for each source shard."""
    from repro.core.types import init_clients, init_stats
    from repro.dm import DMCache
    s = cluster.n_shards
    sh = cluster.dm.clients.rng.sharding
    clients = jax.device_put(init_clients(cfg, s * s * lanes_per_shard,
                                          seed), sh)
    stats = jax.device_put(jax.tree.map(
        lambda x: jnp.zeros((s,), x.dtype), init_stats()), sh)
    return cluster._replace(dm=DMCache(cluster.dm.state, clients, stats),
                            lanes_per_shard=lanes_per_shard)


def load_imbalance(stats) -> float:
    """The busiest shard's operations (gets + sets) over the shards' mean,
    from per-shard counters."""
    ops = (np.asarray(stats.gets, np.int64)
           + np.asarray(stats.sets, np.int64))
    return float(ops.max() / ops.mean())


def run(job, t_start: float, program=None) -> loop.Outcome:
    """One run of the cell.  ``program`` stands in for ``execute`` (the
    fault tests break the timed path through it)."""
    _program(job.config)
    from repro.core import execute as repro_execute
    from repro.dm import Cluster

    execute = program or repro_execute
    cfg = program_config(job.config)
    s = job.config["n_shards"]
    clock = loop.Phases()
    tr, (fill_seed, lane_seed), salt = traffic(job)
    salt = records.salt(salt)
    clock.done(f"traffic ({tr.keys.size} requests)")
    cluster = Cluster.make(cfg, s, job.config["fill_lanes"] // s,
                           seed=fill_seed)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:s])
    clock.done(f"Cluster.make ({s} shards; peak {peak} bytes in use on "
               "the fullest chip)")
    # the fill goes in calls of a few rounds: a call routes all its rounds
    # up front, and 1,280 at once would hold 0.4 GB more on every chip
    per_call = job.config["fill_rounds_per_call"]
    fill_exec = cfg.split()[1]
    fill_exec = dataclasses.replace(
        fill_exec, route_factor=job.config["fill_route_factor"])
    fill_hits = []
    for r in range(0, tr.fill_keys.shape[0], per_call):
        keys = tr.fill_keys[r:r + per_call]
        fill = execute(cluster, keys, is_write=keys != 0, plan=None,
                       exec_cfg=fill_exec)
        fill_hits.append(np.asarray(fill.hits))
        cluster = fill.cache
        del fill
    fill_hits = np.concatenate(fill_hits)
    state = cluster.dm.state
    cluster = cluster._replace(dm=cluster.dm._replace(state=state._replace(
        values=records.write(state.values, state.key, salt))))
    del state
    clock.done(f"fill ({tr.fill_keys.shape[0]} rounds) and records")
    cluster = fresh_lanes(cluster, cfg, job.config["lanes"] // s, lane_seed)
    cluster, out, lane_hits, rows = loop.serve(job, t_start, cluster,
                                               execute, tr)
    out.load_imbalance = load_imbalance(cluster.dm.stats)
    if out.traced is not None:
        loop.log("traced calls, ms a call: " + ", ".join(
            f"{p} {stages.phase_ms_per_call(out.traced, p)}"
            for p in ("launch", "wait", "fetch")))
    # One chip's share of the traced rounds' work, for the per-chip
    # roofline (the configuration's "assumed")
    out.traced_counters = {k: v / s for k, v in out.traced_counters.items()}
    got = dict(loop.read_back(cluster), fill_hits=fill_hits,
               lane_hits=lane_hits,
               record_class=np.asarray(records.classes(
                   cluster.state.values, cluster.state.key, salt)))
    del cluster
    clock = loop.Phases()
    want = replay(job.config, tr, fill_seed, lane_seed, rows)
    clock.done(f"reference ({rows} rounds after the fill)")
    out.checks = loop.compare(got, want, rows * tr.keys.shape[1],
                              job.config["limits"])
    return out


def _on_nodes(tree, s: int):
    """The reference's node axis laid over the first ``s`` devices where
    there are that many (each node's table on a chip of its own), else
    left where it is."""
    devs = jax.devices()
    if len(devs) < s:
        return tree
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devs[:s]), ("node",))
    return jax.device_put(tree, NamedSharding(mesh, P("node")))


def replay(config: dict, tr, fill_seed: int, lane_seed: int, rows: int,
           control: bool = False) -> dict:
    """The reference's account of the same run: the fill at the fill
    width, then the first ``rows`` rounds at the cell's lanes.  With
    ``control`` it is computed a precision narrower (the control).  Each
    node runs on a device of its own where there are enough: placement
    only, the same operations."""
    s = config["n_shards"]
    pool, wdt = ref.precision(pool_of(config), control)
    cl = rdm.Cluster(pool, s, config["fill_route_factor"])
    tb = _on_nodes(rdm.empty_tables(cl, wdt), s)
    ln = _on_nodes(rdm.new_lanes(cl, config["fill_lanes"] // s, fill_seed,
                                 wdt), s)
    tb, ln, fill_hits, counts = rdm.replay(cl, tb, ln, tr.fill_keys,
                                           tr.fill_keys != 0)
    if counts["route_drops"]:
        raise RuntimeError(f"the fill dropped {counts['route_drops']} "
                           "SETs at its route capacity: raise "
                           "fill_route_factor")
    cl = rdm.Cluster(pool, s, config["route_factor"])
    # set-up writes every slot's record after the fill
    tb = tb._replace(written=jnp.zeros_like(tb.written))
    del ln
    ln = _on_nodes(rdm.new_lanes(cl, config["lanes"] // s, lane_seed, wdt),
                   s)
    tb, ln, lane_hits, counts = rdm.replay(cl, tb, ln, tr.keys[:rows],
                                           tr.writes[:rows])
    # node d's slots are the program's global slots [d * n, (d + 1) * n)
    out = {c: np.asarray(tb.column(c)).reshape(-1) for c in loop.COLUMNS}
    out.update({c: np.asarray(getattr(tb, c)) for c in loop.SCALARS})
    e = len(pool.experts)
    out.update(weights=np.asarray(tb.weights, np.float32),
               local_w=np.asarray(ln.local_w, np.float32).reshape(-1, e),
               fill_hits=fill_hits, lane_hits=lane_hits,
               record_class=records.expected(
                   np.asarray(tb.written).reshape(-1)),
               counters=counts)
    return out
