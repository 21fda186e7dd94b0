"""Disaggregated-memory runtime: the Ditto cache sharded over a device mesh.

Mapping (DESIGN.md §2): every device hosts (a) one shard of the memory pool
— a contiguous bucket range of the sample-friendly table — and (b) a group
of client lanes. Clients hash keys to the owning pool shard and route the
batch with an `all_to_all` (the RDMA network analogue); each shard then
executes the ordinary client-centric `access()` against its local bucket
slice; results route back by reversing the exchange.

Decoupling survives the co-location: pool capacity is a per-shard runtime
scalar (grow/shrink without touching data) and the client-lane count per
device is a batch width (compute elasticity without touching the pool).

The lazy weight update (§4.3.2) becomes a periodic `psum` of the batched
penalty aggregates across all shards — the "RPC to the MN controller".
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.cache import access_group, apply_penalties
from repro.core.hashing import bucket_of, hash_key, splitmix32
from repro.core.types import (CacheConfig, CacheState, ClientState, OpStats,
                              init_cache, init_clients, init_stats,
                              split_tenant_budgets, stats_add)

AXIS = "pool"

# Salt decorrelating the replica-pick hash from the bucket hash: a request
# whose key lands in bucket b must not always pick the same replica side as
# every other key in b, or the "fan reads across replicas" load split
# degenerates per bucket.
_PICK_SALT = jnp.uint32(0x9E3779B9)


class DMCache(NamedTuple):
    state: CacheState      # slot arrays sharded over AXIS (bucket ranges)
    clients: ClientState   # client lanes sharded over AXIS
    stats: OpStats         # per-shard counters (psum at read time)


class Membership(NamedTuple):
    """Routing-time cluster membership, threaded through the DM drivers as
    dynamic (traced) arrays so failover/replica changes never recompile.

    ``primary``/``replica`` are the ROUTER's view (what `Cluster.membership`
    computed from the shards it believes alive); ``serving`` is ground
    truth.  Between a failure and its heartbeat detection the two disagree:
    requests still route to the dead shard and bounce (counted in
    ``route_drops`` — the timeout analogue), which is exactly the
    detection-latency dip the failover benchmark measures.
    """
    primary: jnp.ndarray   # i32[global_buckets] owner shard per bucket
    replica: jnp.ndarray   # i32[global_buckets] secondary (n_shards = none)
    serving: jnp.ndarray   # bool[n_shards] ground-truth liveness


def identity_membership(n_shards: int, global_buckets: int) -> Membership:
    """The no-replication, all-alive membership: bit-identical routing to
    the pre-Membership router (owner = bucket // local_buckets)."""
    local_buckets = global_buckets // n_shards
    return Membership(
        primary=jnp.arange(global_buckets, dtype=jnp.int32) // local_buckets,
        replica=jnp.full((global_buckets,), n_shards, jnp.int32),
        serving=jnp.ones((n_shards,), bool))


def _pad_clients(clients: ClientState, n: int) -> ClientState:
    """Present a shard's client lanes as n request lanes (q-padded).

    Replicating lanes verbatim would duplicate their `rng` streams —
    padded lanes would fold in the same key and produce identical sample
    offsets / expert choices (correlated evictions). The lane index is
    folded into every padded-tail key so each presented lane draws an
    independent stream; the original lanes keep their stored keys."""
    lanes = clients.fc_slot.shape[0]

    def pad(x):
        reps = -(-n // x.shape[0])
        return jnp.concatenate([x] * reps, axis=0)[:n]

    padded = jax.tree.map(pad, clients)
    idx = jnp.arange(n, dtype=jnp.uint32)
    folded = jax.vmap(jax.random.fold_in)(padded.rng, idx)
    rng = jnp.where((idx < lanes)[:, None], padded.rng, folded)
    return padded._replace(rng=rng)


def _unpad_clients(orig: ClientState, padded: ClientState,
                   lanes: int) -> ClientState:
    def cut(o, p):
        return p[:lanes] if p.shape[0] >= lanes else o
    return jax.tree.map(cut, orig, padded)


def _mesh(n: int) -> Mesh:
    """A 1-D pool mesh over the first ``n`` devices of this process (all
    of them when it has fewer)."""
    devs = jax.devices()[:n]
    return jax.make_mesh((len(devs),), (AXIS,), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))


def dm_make(cfg: CacheConfig, n_shards: int, lanes_per_shard: int,
            seed: int = 0) -> Tuple[Mesh, "DMCache", CacheConfig]:
    """Deprecated: build clusters through ``repro.dm.Cluster.make`` (the
    one membership handle — mesh, topology, replica map, liveness).  This
    shim returns the same (mesh, DMCache, local_cfg) triple, bit-identical
    to ``Cluster.make(...)``'s fields."""
    from repro.core.cache import _deprecated_entrypoint
    _deprecated_entrypoint("dm_make")
    return _dm_make_impl(cfg, n_shards, lanes_per_shard, seed)


def _dm_make_impl(cfg: CacheConfig, n_shards: int, lanes_per_shard: int,
                  seed: int = 0) -> Tuple[Mesh, "DMCache", CacheConfig]:
    """Build a sharded cache. cfg describes the GLOBAL pool; each shard
    runs a local core cache over 1/n_shards of the buckets/capacity."""
    assert cfg.n_buckets % n_shards == 0
    assert cfg.capacity % n_shards == 0
    assert cfg.capacity_blocks % n_shards == 0
    local = dataclasses.replace(
        cfg, n_buckets=cfg.n_buckets // n_shards,
        capacity=cfg.capacity // n_shards,
        capacity_blocks=cfg.capacity_blocks // n_shards,
        hist_len=cfg.history_len // n_shards)
    mesh = _mesh(n_shards)
    state = init_cache(cfg)  # global arrays; shard by slot ranges
    # Per-shard scalars (n_cached, hist_ctr, ...) must exist per shard:
    def rep(x):
        return jnp.broadcast_to(x[None], (n_shards,) + x.shape)
    state = state._replace(
        n_cached=rep(state.n_cached), bytes_cached=rep(state.bytes_cached),
        hist_ctr=rep(state.hist_ctr),
        clock=rep(state.clock), weights=rep(state.weights),
        gds_L=rep(state.gds_L),
        capacity_blocks=rep(jnp.asarray(local.budget_blocks, jnp.int32)),
        tenant_bytes=rep(state.tenant_bytes),
        l0_epoch=rep(state.l0_epoch),
        # Exact per-shard split (column sums == the global budgets).
        tenant_budget=jnp.asarray(
            split_tenant_budgets(cfg.tenant_budgets, n_shards)))
    clients = init_clients(cfg, n_shards * lanes_per_shard, seed)

    sh_slot = NamedSharding(mesh, P(AXIS))
    sh_scalar = NamedSharding(mesh, P(AXIS))

    state = jax.tree.map(lambda x: jax.device_put(x, sh_slot), state)
    clients = jax.tree.map(lambda x: jax.device_put(x, sh_slot), clients)
    stats = jax.tree.map(lambda x: jnp.zeros((n_shards,), x.dtype),
                         init_stats())
    stats = jax.tree.map(lambda x: jax.device_put(x, sh_scalar), stats)
    return mesh, DMCache(state, clients, stats), local


def _squeeze_shard(state: CacheState, stats: OpStats):
    """Shard-local scalars arrive in shard_map as [1]-slices; squeeze."""
    state = state._replace(
        n_cached=state.n_cached[0], bytes_cached=state.bytes_cached[0],
        hist_ctr=state.hist_ctr[0],
        clock=state.clock[0], weights=state.weights[0],
        gds_L=state.gds_L[0], capacity_blocks=state.capacity_blocks[0],
        tenant_bytes=state.tenant_bytes[0],
        tenant_budget=state.tenant_budget[0],
        l0_epoch=state.l0_epoch[0])
    return state, jax.tree.map(lambda x: x[0], stats)


def _expand_shard(state: CacheState, stats: OpStats):
    """Re-expand shard scalars for the sharded output layout."""
    state = state._replace(
        n_cached=state.n_cached[None], bytes_cached=state.bytes_cached[None],
        hist_ctr=state.hist_ctr[None],
        clock=state.clock[None], weights=state.weights[None],
        gds_L=state.gds_L[None], capacity_blocks=state.capacity_blocks[None],
        tenant_bytes=state.tenant_bytes[None],
        tenant_budget=state.tenant_budget[None],
        l0_epoch=state.l0_epoch[None])
    return state, jax.tree.map(lambda x: x[None], stats)


def _make_route_one(local_cfg: CacheConfig, n_shards: int, lanes: int,
                    q: int):
    """Per-round client-side router: decide owners from the Membership
    maps, pack per-destination request blocks.  Pure function of the keys
    and membership (state-independent), which is exactly what lets
    ``dm_execute`` route group k+1 while group k is still executing.

    Replication (DESIGN.md §14): a bucket with a secondary replica fans
    its reads across both copies — a deterministic per-request rendezvous
    bit (``splitmix32(key_hash ^ salt)``) picks the side, so reference and
    fused backends make bit-equal routing decisions.  Writes go to the
    primary AND emit a write-through mirror to the secondary; mirrors ride
    the same packing pass as lane indices [lanes, 2*lanes), carry the
    shadow sideband bit, and sort after every real request of the same
    destination, so a membership with no replicas packs bit-identically
    to the legacy single-owner router."""
    global_buckets = local_cfg.n_buckets * n_shards
    L2 = 2 * lanes

    def route_one(keys_l, write_l, size_l, ten_l, member):
        kh = hash_key(keys_l)
        bkt = bucket_of(kh, global_buckets)
        primary = member.primary[bkt]
        sec = member.replica[bkt]
        live = keys_l != 0
        has_sec = (sec < n_shards) & (sec != primary)
        # Deterministic replica fan-out for reads: pure hash of the key,
        # independent of cache state and backend.
        pick = (splitmix32(kh ^ _PICK_SALT) & 1).astype(bool)
        owner = jnp.where(has_sec & ~write_l & pick, sec, primary)
        # no-op lanes (key 0) route nowhere and never consume capacity
        owner = jnp.where(live, owner, n_shards)
        # Write-through mirror copies for replicated buckets (shadow ops).
        mirror = live & write_l & has_sec
        keys_c = jnp.concatenate([keys_l, jnp.where(mirror, keys_l, 0)])
        owner_c = jnp.concatenate([owner, jnp.where(mirror, sec, n_shards)])
        write_c = jnp.concatenate([write_l, write_l])
        size_c = jnp.concatenate([size_l, size_l])
        ten_c = jnp.concatenate([ten_l, ten_l])
        shadow_c = jnp.concatenate([jnp.zeros((lanes,), bool),
                                    jnp.ones((lanes,), bool)])
        # rank within destination
        # Segment packing, not priority ranking: a stable sort by owner
        # is the one-shot way to pack per-destination request blocks
        # (argmin-peel would cost O(lanes) peels).  dittolint: disable=DL003
        order = jnp.argsort(owner_c * (L2 + 1)
                            + jnp.arange(L2, dtype=owner_c.dtype))
        sorted_owner = owner_c[order]
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 sorted_owner[1:] != sorted_owner[:-1]])
        seg_start = jax.lax.cummax(jnp.where(first, jnp.arange(L2), 0))
        rank = jnp.arange(L2) - seg_start
        send = jnp.zeros((n_shards, q), jnp.uint32)
        wsend = jnp.zeros((n_shards, q), bool)
        zsend = jnp.ones((n_shards, q), jnp.uint32)
        nsend = jnp.zeros((n_shards, q), jnp.uint32)
        shsend = jnp.zeros((n_shards, q), bool)
        src_slot = jnp.zeros((n_shards, q), jnp.int32) - 1
        ok = rank < q
        dst = jnp.where(ok, sorted_owner, n_shards)
        rr = jnp.where(ok, rank, 0)
        send = send.at[dst, rr].set(keys_c[order], mode="drop")
        wsend = wsend.at[dst, rr].set(write_c[order], mode="drop")
        zsend = zsend.at[dst, rr].set(size_c[order], mode="drop")
        nsend = nsend.at[dst, rr].set(ten_c[order], mode="drop")
        shsend = shsend.at[dst, rr].set(shadow_c[order], mode="drop")
        src_slot = src_slot.at[dst, rr].set(order.astype(jnp.int32),
                                            mode="drop")
        # Requests beyond the per-destination capacity are NOT executed
        # this step (the caller sees hit=False and may reissue); count
        # them so skewed-trace hit ratios stay honest.  Dropped MIRRORS
        # are replica staleness, not lost client ops — separate counter.
        over = ~ok & (keys_c[order] != 0)
        n_drop = jnp.sum(over & (order < lanes)).astype(jnp.int32)
        n_rep_drop = jnp.sum(over & (order >= lanes)).astype(jnp.int32)
        # The op sideband word (tenant id << 10 | shadow << 9 |
        # object size << 1 | write bit) rides as a second u32 of the
        # SAME collective.
        meta = ((nsend.astype(jnp.uint32) << 10)
                | (shsend.astype(jnp.uint32) << 9)
                | (zsend.astype(jnp.uint32) << 1)
                | wsend.astype(jnp.uint32))
        packed = jnp.stack([send, meta], axis=-1)          # [S, q, 2]
        return packed, src_slot, n_drop, n_rep_drop

    return route_one


def _unpack_recv(precv, n_shards: int, q: int):
    """Split a received [G, S, q, 2] exchange back into op tensors."""
    G = precv.shape[0]
    recv = precv[..., 0].reshape(G, n_shards * q)
    wrecv = (precv[..., 1] & 1).astype(bool).reshape(G, n_shards * q)
    zrecv = ((precv[..., 1] >> 1) & 0xFF).reshape(G, n_shards * q)
    shrecv = ((precv[..., 1] >> 9) & 1).astype(bool).reshape(
        G, n_shards * q)
    nrecv = (precv[..., 1] >> 10).reshape(G, n_shards * q)
    return recv, wrecv, zrecv, nrecv, shrecv


def _bounce_dead(member: Membership, recv, shrecv):
    """Ground-truth liveness gate on the memory-pool side: a request that
    arrives at a non-serving shard is lost (the RDMA timeout analogue).
    Bounced keys become no-op lanes — never executed, never counted as
    misses — and are tallied as drops (real → route_drops, mirror →
    replica_drops) so ``issued == gets + sets + route_drops`` survives
    the detection window."""
    up = member.serving[jax.lax.axis_index(AXIS)]
    bounced = (recv != 0) & ~up
    n_real = jnp.sum(bounced & ~shrecv).astype(jnp.int32)
    n_shadow = jnp.sum(bounced & shrecv).astype(jnp.int32)
    return jnp.where(up, recv, 0), n_real, n_shadow


def _back_merge(hit_back, src_slot, lanes: int):
    """Merge one round's returned [S, q] hit block back onto its source
    lanes (reverse of the routing scatter).  Mirror entries (src_slot >=
    lanes) are replication traffic: excluded, so the client sees exactly
    its primary/picked-replica reply — and the scatter below never gets
    an out-of-range index to clip."""
    valid = (src_slot >= 0) & (src_slot < lanes)
    return jnp.zeros((lanes,), bool).at[
        jnp.where(valid, src_slot, 0).reshape(-1)].max(
        jnp.where(valid, hit_back, False).reshape(-1))


def _sync_weights(local_cfg: CacheConfig, state: CacheState,
                  clients: ClientState):
    """Lazy weight update: periodic psum of penalty aggregates — the
    'RPC to the MN controller' (§4.3.2), shared by both DM drivers."""
    tot = jnp.sum(clients.penalty_cnt)
    # All shards agree on the sync decision (consistent global weights).
    do_sync = jax.lax.pmax((tot >= local_cfg.sync_period).astype(
        jnp.int32), AXIS) > 0
    pen = jnp.sum(clients.penalty_acc, axis=0)
    pen_global = jax.lax.psum(jnp.where(do_sync, pen, 0.0), AXIS)
    lam = jnp.float32(local_cfg.learning_rate)
    # Shared clamp-then-normalize update (core/cache.py): global
    # weights sum to exactly 1 on the DM path too.
    w = apply_penalties(state.weights, pen_global, lam)
    state = state._replace(weights=jnp.where(do_sync, w, state.weights))
    clients = clients._replace(
        penalty_acc=jnp.where(do_sync, 0.0, clients.penalty_acc),
        penalty_cnt=jnp.where(do_sync, 0, clients.penalty_cnt),
        local_weights=jnp.where(
            do_sync, jnp.broadcast_to(w, clients.local_weights.shape),
            clients.local_weights))
    return state, clients


def _route_capacity(lanes: int, n_shards: int, route_factor: int) -> int:
    if route_factor <= 0:
        return lanes
    return max(1, min(lanes, route_factor * lanes // n_shards + 1))


def dm_access(mesh: Mesh, local_cfg: CacheConfig, dm: DMCache,
              keys: jnp.ndarray, is_write=None, obj_size=None,
              tenant=None,
              route_factor: int = 4,
              member: Membership | None = None,
              ) -> Tuple[DMCache, jnp.ndarray]:
    """Deprecated single-step DM driver: drive traces through
    ``repro.core.execute`` or :func:`dm_execute` (the pipelined scan is
    bit-equal to calling this once per step, and overlaps the next
    group's exchange with the current group's execution)."""
    from repro.core.cache import _deprecated_entrypoint
    _deprecated_entrypoint("dm_access")
    return _dm_access_impl(mesh, local_cfg, dm, keys, is_write, obj_size,
                           tenant, route_factor, member)


def _dm_access_impl(mesh: Mesh, local_cfg: CacheConfig, dm: DMCache,
                    keys: jnp.ndarray, is_write=None, obj_size=None,
                    tenant=None,
                    route_factor: int = 4,
                    member: Membership | None = None,
                    ) -> Tuple[DMCache, jnp.ndarray]:
    """One DM step: keys [n_shards * lanes] or a request group
    [G, n_shards * lanes] (0 = no-op). Returns hits of the same shape.
    ``obj_size`` ([.. like keys], 64B blocks, default 1) is bit-packed
    with the write flag into a second u32 word of the keys' exchange,
    so the owning shard charges the byte-accurate insert cost of each
    routed request without an extra collective.  ``tenant`` ([.. like
    keys], ids in [0, n_tenants)) rides the same sideband word (bits
    9+), so multi-tenant budget enforcement needs no extra collective
    either; ignored when ``local_cfg.n_tenants == 1``.

    Batched routing: the router packs each round of the group into
    per-destination request blocks, ships the whole [G, q] group per
    destination in ONE exchange (the batched one-RTT pipeline), and the
    owning shard executes the group as a single widened
    ``access_group`` step.

    Routing capacity: each source shard can send up to
    ``q = min(lanes, route_factor * lanes / n_shards + 1)`` requests to
    any one destination shard per round (``route_factor <= 0`` means
    full capacity, q = lanes: no request can ever be dropped). Requests
    beyond the capacity — possible only under extreme key skew — are
    *counted* in ``OpStats.route_drops`` (they behave like failed-CAS
    retries: callers subtract them from issued ops, they are never
    silently lost; see DESIGN.md §2).

    ``member`` (a :class:`Membership`, default identity) supplies the
    failover/replication routing maps; see DESIGN.md §14."""
    n_shards = mesh.shape[AXIS]
    squeeze = keys.ndim == 1
    if squeeze:
        keys = keys[None]
        if is_write is not None:
            is_write = is_write[None]
        if obj_size is not None:
            obj_size = obj_size[None]
        if tenant is not None:
            tenant = tenant[None]
    G = keys.shape[0]
    lanes = keys.shape[1] // n_shards
    q = _route_capacity(lanes, n_shards, route_factor)

    if is_write is None:
        is_write = jnp.zeros_like(keys, dtype=bool)
    if obj_size is None:
        obj_size = jnp.ones_like(keys, dtype=jnp.uint32)
    if tenant is None:
        tenant = jnp.zeros_like(keys, dtype=jnp.uint32)
    if member is None:
        member = identity_membership(n_shards,
                                     local_cfg.n_buckets * n_shards)

    route_one = _make_route_one(local_cfg, n_shards, lanes, q)

    def step(state, clients, stats, keys_l, write_l, size_l, ten_l, mem):
        state, stats = _squeeze_shard(state, stats)
        # --- per-round routing: group blocks per destination ------------
        # The sideband word carries size (bits 1-8) + shadow (bit 9) +
        # tenant (bits 10+), so sizes are clipped to the engine's own
        # 8-bit clamp (the access path clips identically — bit-identical
        # results).
        size_c = jnp.clip(size_l, 1, 254).astype(jnp.uint32)
        packed, src_slot, n_drop, n_rep_drop = jax.vmap(
            route_one, in_axes=(0, 0, 0, 0, None))(
            keys_l, write_l, size_c, ten_l, mem)           # [G, S, q, 2]
        # --- the network: ONE exchange ships each destination's whole
        # [G, q] request group (RDMA doorbell-batching analogue) ---------
        precv = jax.lax.all_to_all(packed, AXIS, 1, 1, tiled=True)
        recv, wrecv, zrecv, nrecv, shrecv = _unpack_recv(precv, n_shards, q)
        recv, n_bnc, n_bnc_sh = _bounce_dead(mem, recv, shrecv)

        # --- memory-pool side: one widened client-centric group step ----
        state, clients2, stats, res = access_group(
            local_cfg, state, _pad_clients(clients, n_shards * q), stats,
            recv, is_write=wrecv, obj_size=zrecv, tenant=nrecv,
            shadow=shrecv)
        stats = stats_add(stats, route_drops=jnp.sum(n_drop) + n_bnc,
                          replica_drops=jnp.sum(n_rep_drop) + n_bnc_sh)

        # --- route replies back + merge hit masks -----------------------
        hits = jax.vmap(
            lambda hb, ss: _back_merge(hb, ss, lanes))(
            jax.lax.all_to_all(res.hit.reshape(G, n_shards, q),
                               AXIS, 1, 1, tiled=True), src_slot)

        # --- lazy weight update: periodic psum of penalty aggregates ----
        clients = _unpad_clients(clients, clients2, lanes)
        state, clients = _sync_weights(local_cfg, state, clients)
        state, stats = _expand_shard(state, stats)
        return state, clients, stats, hits

    spec_state = jax.tree.map(lambda _: P(AXIS), dm.state)
    spec_clients = jax.tree.map(lambda _: P(AXIS), dm.clients)
    spec_stats = jax.tree.map(lambda _: P(AXIS), dm.stats)

    spec_member = jax.tree.map(lambda _: P(), member)

    # Jitted so an eager caller runs the same program a traced one does
    # (an eager shard_map call fails JAX's output-sharding check here).
    fn = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(spec_state, spec_clients, spec_stats,
                  P(None, AXIS), P(None, AXIS), P(None, AXIS),
                  P(None, AXIS), spec_member),
        out_specs=(spec_state, spec_clients, spec_stats, P(None, AXIS)),
        check_vma=False))
    state, clients, stats, hits = fn(dm.state, dm.clients, dm.stats,
                                     keys, is_write, obj_size,
                                     tenant.astype(jnp.uint32), member)
    if squeeze:
        hits = hits[0]
    return DMCache(state, clients, stats), hits


def dm_execute(mesh: Mesh, local_cfg: CacheConfig, dm: DMCache,
               keys: jnp.ndarray, is_write=None, obj_size=None,
               tenant=None,
               route_factor: int = 4,
               member: Membership | None = None,
               ) -> Tuple[DMCache, jnp.ndarray]:
    """Pipelined DM driver: execute a whole sequence of request groups in
    ONE sharded scan, overlapping the router's ``all_to_all`` for group
    k+1 with ``access_group`` for group k.

    ``keys`` is [T, n_shards * lanes] (sequence of single rounds) or
    [NG, G, n_shards * lanes] (sequence of width-G groups); hits come
    back in the same leading shape.  Bit-equal to calling
    :func:`dm_access` once per leading index: routing is a pure function
    of the keys (state-independent), so every group's exchange can be
    issued before the previous group's table access commits — the scan
    carry holds the *received* buffer for the current group while the
    next exchange is already in flight (double buffering).  Per-step
    host dispatch, jit retraces and device round-trips collapse into one
    compiled program; the epilogue issues one extra (discarded) exchange
    for the wrapped tail group.

    Weight sync, route-drop accounting and the op sideband word are the
    exact per-step code paths (shared helpers), executed in the same
    order inside the scan body."""
    n_shards = mesh.shape[AXIS]
    flat = keys.ndim == 2
    if flat:
        keys = keys[:, None, :]
        if is_write is not None:
            is_write = is_write[:, None, :]
        if obj_size is not None:
            obj_size = obj_size[:, None, :]
        if tenant is not None:
            tenant = tenant[:, None, :]
    NG, G = keys.shape[0], keys.shape[1]
    lanes = keys.shape[2] // n_shards
    q = _route_capacity(lanes, n_shards, route_factor)

    if is_write is None:
        is_write = jnp.zeros_like(keys, dtype=bool)
    if obj_size is None:
        obj_size = jnp.ones_like(keys, dtype=jnp.uint32)
    if tenant is None:
        tenant = jnp.zeros_like(keys, dtype=jnp.uint32)
    tenant = tenant.astype(jnp.uint32)
    if member is None:
        member = identity_membership(n_shards,
                                     local_cfg.n_buckets * n_shards)

    if NG == 0:
        return dm, (jnp.zeros((0, keys.shape[2]), bool) if flat
                    else jnp.zeros(keys.shape, bool))

    route_one = _make_route_one(local_cfg, n_shards, lanes, q)

    def run(state, clients, stats, keys_l, write_l, size_l, ten_l, mem):
        state, stats = _squeeze_shard(state, stats)
        size_c = jnp.clip(size_l, 1, 254).astype(jnp.uint32)
        # Route EVERY group up front — routing reads only the keys and
        # the (step-constant) membership, so this is exact, and it is
        # what the pipeline overlaps.
        packed, src_slot, n_drop, n_rep_drop = jax.vmap(
            jax.vmap(route_one, in_axes=(0, 0, 0, 0, None)),
            in_axes=(0, 0, 0, 0, None))(
            keys_l, write_l, size_c, ten_l, mem)     # [NG, G, S, q, 2]
        # Summed once == added once per step (integer counter).
        stats = stats_add(stats, route_drops=jnp.sum(n_drop),
                          replica_drops=jnp.sum(n_rep_drop))

        # Prologue: group 0's exchange fills the first recv buffer.
        recv0 = jax.lax.all_to_all(packed[0], AXIS, 1, 1, tiled=True)
        # Scan inputs are each step's NEXT group (wrapped tail: the last
        # step re-sends group 0 and discards the reply).
        nxt = jnp.concatenate([packed[1:], packed[:1]], axis=0)

        def body(carry, xs):
            state, clients, stats, precv = carry
            pnxt, ss = xs
            # Issue the NEXT exchange before touching the table: it
            # depends only on pre-routed keys, never on the carry, so
            # the scheduler can run it concurrently with this group's
            # access_group (the double-buffer overlap).
            precv_next = jax.lax.all_to_all(pnxt, AXIS, 1, 1, tiled=True)
            recv, wrecv, zrecv, nrecv, shrecv = _unpack_recv(
                precv, n_shards, q)
            recv, n_bnc, n_bnc_sh = _bounce_dead(mem, recv, shrecv)
            stats = stats_add(stats, route_drops=n_bnc,
                              replica_drops=n_bnc_sh)
            state, clients2, stats, res = access_group(
                local_cfg, state, _pad_clients(clients, n_shards * q),
                stats, recv, is_write=wrecv, obj_size=zrecv, tenant=nrecv,
                shadow=shrecv)
            hits = jax.vmap(
                lambda hb, s: _back_merge(hb, s, lanes))(
                jax.lax.all_to_all(res.hit.reshape(G, n_shards, q),
                                   AXIS, 1, 1, tiled=True), ss)
            clients = _unpad_clients(clients, clients2, lanes)
            state, clients = _sync_weights(local_cfg, state, clients)
            return (state, clients, stats, precv_next), hits

        (state, clients, stats, _), hits = jax.lax.scan(
            body, (state, clients, stats, recv0), (nxt, src_slot))
        state, stats = _expand_shard(state, stats)
        return state, clients, stats, hits

    spec_state = jax.tree.map(lambda _: P(AXIS), dm.state)
    spec_clients = jax.tree.map(lambda _: P(AXIS), dm.clients)
    spec_stats = jax.tree.map(lambda _: P(AXIS), dm.stats)
    spec_member = jax.tree.map(lambda _: P(), member)

    # Jitted so an eager caller runs the same program a traced one does
    # (an eager shard_map call fails JAX's output-sharding check here).
    fn = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(spec_state, spec_clients, spec_stats,
                  P(None, None, AXIS), P(None, None, AXIS),
                  P(None, None, AXIS), P(None, None, AXIS), spec_member),
        out_specs=(spec_state, spec_clients, spec_stats,
                   P(None, None, AXIS)),
        check_vma=False))
    state, clients, stats, hits = fn(dm.state, dm.clients, dm.stats,
                                     keys, is_write, obj_size, tenant,
                                     member)
    if flat:
        hits = hits[:, 0, :]
    return DMCache(state, clients, stats), hits


def dm_set_capacity(dm: DMCache, new_global_capacity: int,
                    n_shards: int) -> DMCache:
    """Deprecated elastic memory resize (budget in 64B blocks): use
    ``Cluster.with_capacity(blocks)`` — the membership handle carries
    mesh/n_shards, so nothing is re-threaded positionally.  Bit-identical
    pass-through (one scalar write per shard, no migration)."""
    from repro.core.cache import _deprecated_entrypoint
    _deprecated_entrypoint("dm_set_capacity")
    from repro.elastic.resize import _set_capacity_impl
    return _set_capacity_impl(dm, new_global_capacity, n_shards)
