"""`Cluster`: the one membership handle for the DM runtime (DESIGN.md §14).

Before this module, cluster membership was smeared across the call
surface: `dm_make(cfg, n_shards, lanes_per_shard)` built the mesh,
`dm_set_capacity(dm, cap, n_shards)` and
`resize.set_capacity/set_tenant_budgets/resize_lanes(mesh, ...)` each
re-threaded `mesh`/`n_shards` positionally, and nothing at all modeled
replica sets or shard liveness.  `Cluster` owns all of it — mesh,
topology, replica map, liveness — and `execute()`, the elastic resize
paths and the scenario driver consume the handle; the legacy entrypoints
survive as `DeprecationWarning` shims that are bit-identical
pass-throughs (the PR 8 `run_trace`/`dm_access` pattern).

Liveness is two views, on purpose:

* ``alive`` — ground truth.  `inject_failure(k)` flips it and wipes the
  shard's state (its DRAM is gone); requests that still route to k
  bounce and are counted in ``route_drops`` (the RDMA timeout analogue).
* ``routed`` — the router's belief.  Only `mark_failed(k)` (normally
  driven by the `HealthMonitor`'s missed-heartbeat state machine) flips
  it, at which point `membership()` deterministically re-routes k's
  buckets: replicated buckets promote their live secondary (warm copy
  first), the rest rendezvous-hash across the surviving shards.

Everything `membership()` computes is a pure function of
(alive, routed, replicas), so reruns of a seeded failure timeline route
identically — the determinism the failover tests pin down.  The handle
keeps those maps on its chips (``routes``), made once per membership by
the mutators that change it; a steady-state `execute` moves only its
requests and answers between host and chips.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hashing import splitmix32
from repro.core.types import CacheConfig
from repro.dm.sharded_cache import (AXIS, DMCache, Membership,
                                    _dm_make_impl, dm_execute)

__all__ = ["Cluster", "with_capacity", "with_tenant_budgets", "with_lanes",
           "mark_failed", "replica_map"]


def _rendezvous_scores(n_buckets: int, n_shards: int) -> np.ndarray:
    """i64[n_buckets, n_shards] deterministic rendezvous weights: highest
    score among the eligible shards owns the bucket.  Pure hash of
    (bucket, shard) — membership changes never reshuffle the survivors'
    buckets among themselves (only the dead shard's buckets move)."""
    b = jnp.arange(n_buckets, dtype=jnp.uint32)[:, None]
    s = jnp.arange(n_shards, dtype=jnp.uint32)[None, :]
    score = splitmix32(b * jnp.uint32(2654435761)
                       ^ (s + jnp.uint32(1)) * jnp.uint32(0x85EBCA6B))
    return np.asarray(score).astype(np.int64)


class Cluster(NamedTuple):
    """Immutable cluster handle; every mutator returns a new Cluster."""

    mesh: Mesh
    cfg: CacheConfig               # GLOBAL pool config
    local: CacheConfig             # per-shard slice of it
    dm: DMCache
    n_shards: int
    lanes_per_shard: int
    alive: Tuple[bool, ...]        # ground-truth shard liveness
    routed: Tuple[bool, ...]       # router's liveness view (heartbeats)
    replicas: np.ndarray           # i32[global_buckets] secondary shard
                                   # per bucket; n_shards = unreplicated
    seed: int
    routes: Membership = None      # membership(), replicated on the
                                   # chips; remade by every mutator of
                                   # (alive, routed, replicas)

    # ------------------------------------------------------------------
    # Construction
    # ------------------------------------------------------------------

    @classmethod
    def make(cls, cfg: CacheConfig, n_shards: int = 1,
             lanes_per_shard: int = 8, seed: int = 0) -> "Cluster":
        """Build a sharded cache cluster.  ``cfg`` describes the GLOBAL
        pool; each shard runs a local core cache over 1/n_shards of the
        buckets/capacity (exactly the deprecated ``dm_make`` triple,
        plus the membership the legacy surface never modeled).  Each
        shard is built on its own chip; no device ever holds more than
        one shard's state."""
        mesh, dm, local = _dm_make_impl(cfg, n_shards, lanes_per_shard,
                                        seed)
        return cls(mesh=mesh, cfg=cfg, local=local, dm=dm,
                   n_shards=n_shards, lanes_per_shard=lanes_per_shard,
                   alive=(True,) * n_shards, routed=(True,) * n_shards,
                   replicas=np.full((cfg.n_buckets,), n_shards, np.int32),
                   seed=seed)._rerouted()

    # ------------------------------------------------------------------
    # Handle-shaped views (so ExecResult's delegating properties work on
    # a Cluster exactly as on a core Cache handle).
    # ------------------------------------------------------------------

    @property
    def state(self):
        return self.dm.state

    @property
    def clients(self):
        return self.dm.clients

    @property
    def stats(self):
        """Global (shard-summed) counters; the per-shard arrays stay on
        ``cluster.dm.stats``."""
        from repro.core.types import stats_sum
        return stats_sum(self.dm.stats)

    # ------------------------------------------------------------------
    # Membership → routing maps
    # ------------------------------------------------------------------

    def membership(self) -> Membership:
        """Normalize (alive, routed, replicas) into the traced routing
        maps the DM drivers consume.  Deterministic: identity owners for
        routed home shards; a bucket whose home is marked failed promotes
        its live secondary if it has one (the warm copy), else
        rendezvous-hashes across the routed survivors; dead or
        now-primary secondaries are scrubbed."""
        S, GB = self.n_shards, self.cfg.n_buckets
        lb = self.local.n_buckets
        routed = np.asarray(self.routed, bool)
        prim = np.arange(GB, dtype=np.int32) // lb
        rep = np.asarray(self.replicas, np.int32)
        rep_live = (rep < S) & routed[np.where(rep < S, rep, 0)]
        dead_home = ~routed[prim]
        if dead_home.any() and routed.any():
            sc = _rendezvous_scores(GB, S)
            sc[:, ~routed] = -1
            rv = np.argmax(sc, axis=1).astype(np.int32)
            prim = np.where(dead_home & rep_live, rep,
                            np.where(dead_home, rv, prim)).astype(np.int32)
        rep = np.where(rep_live & (rep != prim), rep, S).astype(np.int32)
        return Membership(primary=jnp.asarray(prim),
                          replica=jnp.asarray(rep),
                          serving=jnp.asarray(np.asarray(self.alive, bool)))

    def _rerouted(self) -> "Cluster":
        """This handle with ``routes`` remade from its membership and
        placed, replicated, on its chips."""
        return self._replace(routes=jax.device_put(
            self.membership(), NamedSharding(self.mesh, P())))

    def replica_map(self) -> np.ndarray:
        """i32[global_buckets] secondary shard per bucket (n_shards =
        unreplicated).  A copy — the handle stays immutable."""
        return np.asarray(self.replicas, np.int32).copy()

    # ------------------------------------------------------------------
    # Hot-bucket replication
    # ------------------------------------------------------------------

    def with_replicas(self, replicas) -> "Cluster":
        """Install an explicit per-bucket secondary map (i32[GB]; use
        ``n_shards`` for 'no replica')."""
        rep = np.asarray(replicas, np.int32)
        if rep.shape != (self.cfg.n_buckets,):
            raise ValueError(
                f"replica map must be [{self.cfg.n_buckets}], "
                f"got {rep.shape}")
        if ((rep < 0) | (rep > self.n_shards)).any():
            raise ValueError("replica shard ids must be in [0, n_shards]")
        return self._replace(replicas=rep.copy())._rerouted()

    def elect_replicas(self, loads, n_hot: int) -> "Cluster":
        """Elect replica sets for the ``n_hot`` hottest buckets from a
        per-global-bucket load vector (the scenario driver's EMA).  The
        secondary is the rendezvous winner among the routed shards
        excluding the bucket's home — deterministic in (bucket, shard),
        so the same loads elect the same replicas on every rerun.
        Buckets with no positive load never get a replica; everything
        not elected is unreplicated."""
        S, GB = self.n_shards, self.cfg.n_buckets
        lb = self.local.n_buckets
        loads = np.asarray(loads, np.float64)
        if loads.shape != (GB,):
            raise ValueError(f"loads must be [{GB}], got {loads.shape}")
        routed = np.asarray(self.routed, bool)
        rep = np.full((GB,), S, np.int32)
        n_hot = int(min(n_hot, GB))
        if n_hot > 0 and routed.sum() >= 2:
            # Host-side election between windows, never traced — the
            # argmin-peel rule targets in-kernel ranking.
            hot = np.argsort(-loads, kind="stable")[:n_hot]  # dittolint: disable=DL003
            hot = hot[loads[hot] > 0]
            prim = (hot // lb).astype(np.int32)
            sc = _rendezvous_scores(GB, S)[hot]
            sc[:, ~routed] = -1
            sc[np.arange(hot.size), prim] = -1
            best = np.argmax(sc, axis=1).astype(np.int32)
            ok = sc[np.arange(hot.size), best] >= 0
            rep[hot[ok]] = best[ok]
        return self._replace(replicas=rep)._rerouted()

    # ------------------------------------------------------------------
    # Elastic resize (the legacy resize surface, handle-shaped)
    # ------------------------------------------------------------------

    def with_capacity(self, new_global_capacity: int) -> "Cluster":
        """One capacity-scalar write per shard, zero migration (the
        paper's elastic resize; replaces ``dm_set_capacity`` /
        ``resize.set_capacity``)."""
        from repro.elastic.resize import _set_capacity_impl
        return self._replace(dm=_set_capacity_impl(
            self.dm, new_global_capacity, self.n_shards))

    def drain_to(self, new_global_capacity: int, *, drain: bool = True,
                 batch_per_shard: int = 64, max_steps: int = 256):
        """Online resize with the shrink drain (`resize_memory`).
        Returns (cluster, ResizeReport)."""
        from repro.elastic.resize import resize_memory
        dm, report = resize_memory(
            self.mesh, self.local, self.dm, new_global_capacity,
            drain=drain, batch_per_shard=batch_per_shard,
            max_steps=max_steps)
        return self._replace(dm=dm), report

    def with_tenant_budgets(self, budgets) -> "Cluster":
        """Rewrite the per-tenant byte budgets (global units; exact
        per-shard split)."""
        from repro.elastic.resize import set_tenant_budgets
        return self._replace(dm=set_tenant_budgets(
            self.dm, budgets, self.n_shards))

    def with_lanes(self, new_lanes_per_shard: int):
        """Change the client-lane width per shard (`resize_lanes`).
        Returns (cluster, ResizeReport)."""
        from repro.elastic.resize import resize_lanes
        dm, report = resize_lanes(self.mesh, self.local, self.dm,
                                  new_lanes_per_shard,
                                  seed=self.seed + 1)
        return self._replace(dm=dm,
                             lanes_per_shard=new_lanes_per_shard), report

    # ------------------------------------------------------------------
    # Failure / recovery
    # ------------------------------------------------------------------

    def inject_failure(self, k: int) -> "Cluster":
        """Ground-truth shard loss: wipe shard k's state and stop it
        serving.  The ROUTER still believes k is up (``routed``
        unchanged) — requests bounce into ``route_drops`` until the
        heartbeat monitor notices and `mark_failed` re-routes.  That gap
        is the detection-latency dip the failover benchmark measures."""
        from repro.elastic.resize import fail_wipe_shard
        if not (0 <= k < self.n_shards):
            raise ValueError(f"shard {k} out of range")
        alive = list(self.alive)
        alive[k] = False
        return self._replace(
            dm=fail_wipe_shard(self.mesh, self.local, self.dm, k),
            alive=tuple(alive))._rerouted()

    def mark_failed(self, k: int) -> "Cluster":
        """Membership action on detection: stop routing to shard k.
        `membership()` then promotes live secondaries for k's replicated
        buckets and rendezvous-reroutes the rest across survivors."""
        if not (0 <= k < self.n_shards):
            raise ValueError(f"shard {k} out of range")
        routed = list(self.routed)
        routed[k] = False
        return self._replace(routed=tuple(routed))._rerouted()

    def recover(self, k: int, *, rewarm: bool = True,
                max_objects: int = 512):
        """Bring a replacement for shard k back into the cluster: serve
        + route again, and (by default) run the recovery drain that
        rewarms k from the survivors (`resize.rewarm_shard` — the
        working set k's buckets accumulated on other shards while it was
        out moves home, hottest first).  Returns (cluster, ResizeReport).
        """
        from repro.elastic.resize import ResizeReport, rewarm_shard
        if not (0 <= k < self.n_shards):
            raise ValueError(f"shard {k} out of range")
        alive = list(self.alive)
        routed = list(self.routed)
        alive[k] = True
        routed[k] = True
        c = self._replace(alive=tuple(alive),
                          routed=tuple(routed))._rerouted()
        if not rewarm:
            return c, ResizeReport(0, 0, 0, 0)
        dm, report = rewarm_shard(c.mesh, c.local, c.dm, k,
                                  max_objects=max_objects)
        return c._replace(dm=dm), report

    # ------------------------------------------------------------------
    # Execution
    # ------------------------------------------------------------------

    def execute(self, keys, is_write=None, obj_size=None, tenant=None,
                route_factor: int = 4):
        """Run a [T, S*lanes] (or [NG, G, S*lanes]) request sequence
        through the pipelined DM driver under this membership.  Returns
        (cluster, hits).  The driver is jitted and cached per
        (mesh, local, route_factor) — membership rides as traced arrays,
        so failover/replica changes never recompile.  On an accelerator
        the input handle's state is donated: it is dead after the call."""
        fn = runner(self.mesh, self.local, route_factor,
                    jax.default_backend() != "cpu")
        dm, hits = fn(self.dm, keys, is_write, obj_size, tenant,
                      member=self.routes)
        return self._replace(dm=dm), hits


_EXEC_CACHE: dict = {}


def runner(mesh: Mesh, local: CacheConfig, route_factor: int,
           donate: bool):
    """The jitted ``dm_execute`` for one cluster shape, cached: called as
    ``fn(dm, keys, is_write, obj_size, tenant, member=routes)``; its
    compiled module is ``jit_cluster_run``.  The pool comes back split
    over the shards as ``Cluster.make`` lays it out, the hits whole on
    every chip.  With ``donate`` the input ``dm`` is donated, so a step
    holds one copy of the pool on each chip."""
    key = (mesh, local, route_factor, donate)
    fn = _EXEC_CACHE.get(key)
    if fn is None:
        def cluster_run(dm, keys, is_write, obj_size, tenant, *, member):
            return dm_execute(mesh, local, dm, keys, is_write, obj_size,
                              tenant, route_factor=route_factor,
                              member=member)
        # Outputs in the layout Cluster.make builds: the steady state runs
        # one program whatever the inputs' layout was.
        fn = _EXEC_CACHE[key] = jax.jit(
            cluster_run, donate_argnums=(0,) if donate else (),
            out_shardings=(NamedSharding(mesh, P(AXIS)),
                           NamedSharding(mesh, P())))
    return fn


# ----------------------------------------------------------------------
# Free-function spellings of the handle's mutators (same contract).
# ----------------------------------------------------------------------

def with_capacity(cluster: Cluster, new_global_capacity: int) -> Cluster:
    return cluster.with_capacity(new_global_capacity)


def with_tenant_budgets(cluster: Cluster, budgets) -> Cluster:
    return cluster.with_tenant_budgets(budgets)


def with_lanes(cluster: Cluster, new_lanes_per_shard: int):
    return cluster.with_lanes(new_lanes_per_shard)


def mark_failed(cluster: Cluster, k: int) -> Cluster:
    return cluster.mark_failed(k)


def replica_map(cluster: Cluster) -> np.ndarray:
    return cluster.replica_map()
