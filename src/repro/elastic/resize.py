"""Online pool resize for the DM runtime (DESIGN.md §8).

The paper's elasticity story has two halves and this module implements
both against the live sharded cache:

* **Memory scale.** Growing the pool is the paper's headline: one
  capacity-scalar write per shard, zero bytes migrated (§2.2). Shrinking
  is where real systems fall over — a capacity clamp alone leaves the
  pool over budget until organic evictions catch up, which can take
  arbitrarily long on a read-heavy trace. `resize_memory` therefore
  *drains* on shrink: bounded batches of priority-ordered evictions per
  shard (lowest priority first under the dominant expert, victims filed
  into the embedded history like any other eviction) until every shard
  is at its new capacity.

* **Compute scale.** Client lanes are just a batch width, but lanes own
  state: the FC cache (§4.2.2) and the lazy-weight-update penalty
  buffers (§4.3.2). `resize_lanes` decommissions lanes by flushing their
  buffered freq deltas into the table and folding their pending expert
  penalties into the global weights (a client shutdown RPC), and brings
  new lanes up with the current global weights and an empty FC cache.

Both paths return a `ResizeReport` with *measured* numbers: migration
bytes are computed from real state deltas (a live key appearing on a
shard it did not occupy before), not asserted to be zero.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import priority as prio
from repro.core.cache import _is_live, _md_view
from repro.core.hashing import bucket_of, hash_key
from repro.core.types import (SIZE_EMPTY, SIZE_HISTORY, CacheConfig,
                              init_clients, split_tenant_budgets, stats_add)

U32 = jnp.uint32
I32 = jnp.int32

# Axis name shared with repro.dm.sharded_cache (kept literal to avoid a
# circular import: dm.sharded_cache delegates dm_set_capacity here).
AXIS = "pool"


class ResizeReport(NamedTuple):
    """Measured outcome of one resize event."""

    migration_bytes: int    # bytes that moved between shards (real delta)
    drained_objects: int    # objects evicted by the shrink drain
    drained_bytes: int      # payload bytes those objects held
    drain_steps: int        # batched drain rounds until at-capacity


def set_capacity(dm, new_global_capacity: int, n_shards: int):
    """Deprecated: use ``Cluster.with_capacity(blocks)`` — the membership
    handle already knows ``n_shards``, so nothing is re-threaded
    positionally.  Bit-identical pass-through to the same scalar write."""
    from repro.core.cache import _deprecated_entrypoint
    _deprecated_entrypoint("set_capacity")
    return _set_capacity_impl(dm, new_global_capacity, n_shards)


def _set_capacity_impl(dm, new_global_capacity: int, n_shards: int):
    """The paper's elastic resize primitive: one scalar write per shard,
    no data movement. The budget is denominated in 64B blocks (resizing
    by GB is ``gb * (1 << 30) // 64`` blocks). Shrinks done through this
    alone leave the pool over budget until organic evictions drain it —
    use `resize_memory` for the online path.

    Multi-tenant pools: this rewrites only the *global* budget; the
    per-tenant split is the arbiter's job (`set_tenant_budgets`)."""
    cap = jnp.full((n_shards,), new_global_capacity // n_shards, jnp.int32)
    return dm._replace(state=dm.state._replace(capacity_blocks=cap))


def set_tenant_budgets(dm, budgets, n_shards: int):
    """Rewrite the per-tenant byte budgets (64B blocks, global units):
    one T-vector write per shard, no data movement — the multi-tenant
    analogue of `set_capacity`, and the primitive the elastic arbiter
    uses to re-split the pool across tenants online (DESIGN.md §11).
    Shard shares sum exactly to the global budgets (a shard whose share
    is 0 simply refuses that tenant's inserts — conservation over
    convenience).

    A tenant shrunk below its occupancy drains organically: its inserts
    are gated off and its own budget-scoped evictions peel it back under
    budget as it keeps issuing traffic."""
    tb = jnp.asarray(split_tenant_budgets(budgets, n_shards))
    tb = jax.device_put(tb, dm.state.tenant_budget.sharding)
    return dm._replace(state=dm.state._replace(tenant_budget=tb))


# ----------------------------------------------------------------------
# Shrink drain: priority-ordered batched evictions per shard.
# ----------------------------------------------------------------------

def _drain_shard(local_cfg: CacheConfig, batch: int, state, stats):
    """Evict up to `batch` lowest-priority live objects on one shard,
    bounded by the shard's *byte* deficit: victims peel off in priority
    order until the freed blocks cover it. Scalars arrive [1]-sliced."""
    names = local_cfg.experts
    E = local_cfg.n_experts
    adaptive = E > 1
    state = state._replace(
        n_cached=state.n_cached[0], bytes_cached=state.bytes_cached[0],
        hist_ctr=state.hist_ctr[0],
        clock=state.clock[0], weights=state.weights[0],
        gds_L=state.gds_L[0], capacity_blocks=state.capacity_blocks[0],
        tenant_bytes=state.tenant_bytes[0],
        tenant_budget=state.tenant_budget[0],
        l0_epoch=state.l0_epoch[0])
    stats = jax.tree.map(lambda x: x[0], stats)

    n_slots = state.key.shape[0]
    deficit = jnp.maximum(state.bytes_cached - state.capacity_blocks, 0)

    live = _is_live(state.size)
    md = _md_view(state, jnp.arange(n_slots))
    prios = prio.priorities(md, names)                       # [n, E]
    # Drain under the dominant expert — the policy the weight vector
    # currently trusts most (same signal opportunistic eviction samples).
    # Per-tenant weight rows ([T, E]) vote as their tenant-mean; for the
    # classic [E] vector this is exactly argmax(weights).
    w_vec = state.weights if state.weights.ndim == 1 \
        else state.weights.mean(axis=0)
    e = jnp.argmax(w_vec)
    pe = jnp.where(live, jnp.take_along_axis(
        prios, jnp.full((n_slots, 1), e), axis=1)[:, 0], jnp.inf)
    order = jnp.argsort(pe)                                  # low prio first
    # Multi-victim byte take: claim the shortest priority-ordered prefix
    # whose summed sizes reach the deficit, at most `batch` victims.
    sz_sorted = jnp.where(live[order], state.size[order].astype(I32), 0)
    freed_before = jnp.cumsum(sz_sorted) - sz_sorted         # exclusive
    take = (live[order] & (freed_before < deficit)
            & (jnp.arange(n_slots) < batch))
    victims = jnp.where(take, order, n_slots)

    # Victims enter the embedded history (§4.3.1) exactly as sampled
    # evictions do, so the adaptive regret signal survives the resize.
    write_hist = take & adaptive & local_cfg.use_lwh
    hist_rank = jnp.cumsum(write_hist.astype(I32)) - 1
    hist_ids = state.hist_ctr + jnp.where(write_hist, hist_rank, 0).astype(U32)
    n_hist = jnp.sum(write_hist).astype(U32)
    bmap = jnp.full((n_slots,), U32(1) << e.astype(U32))

    freed = jnp.sum(jnp.where(take, sz_sorted, 0))           # blocks
    size2 = state.size.at[victims].set(
        jnp.where(write_hist, U32(SIZE_HISTORY), U32(SIZE_EMPTY)), mode="drop")
    ptr2 = state.ptr.at[victims].set(
        jnp.where(write_hist, hist_ids, U32(0)), mode="drop")
    ins2 = state.insert_ts.at[victims].set(bmap, mode="drop")

    n_evict = jnp.sum(take).astype(I32)
    live2 = _is_live(size2)
    n_tenants = state.tenant_bytes.shape[0]
    state = state._replace(
        size=size2, ptr=ptr2, insert_ts=ins2,
        n_cached=state.n_cached - n_evict,
        bytes_cached=jnp.sum(
            jnp.where(live2, size2, U32(0))).astype(I32),
        tenant_bytes=jnp.zeros((n_tenants,), I32).at[
            state.tenant.astype(I32)].add(
            jnp.where(live2, size2, U32(0)).astype(I32)),
        hist_ctr=state.hist_ctr + n_hist,
        # Drain evictions bypass access_group's bucket-version bumps, so
        # a draining shard flushes every lane's L0 via the epoch instead
        # (DESIGN.md §15) — otherwise a near-cache copy of a drained
        # object could keep serving phantom hits.
        l0_epoch=state.l0_epoch + (n_evict > 0).astype(U32))
    # Cost accounting: the drain is a server-driven sweep — one sampling
    # read per victim batch, one CAS per victim, history writes + FAA.
    stats = stats_add(
        stats, rdma_read=jnp.where(n_evict > 0, 1, 0), rdma_cas=n_evict,
        rdma_write=n_hist, rdma_faa=jnp.where(n_hist > 0, 1, 0),
        evictions=n_evict)

    state = state._replace(
        n_cached=state.n_cached[None], bytes_cached=state.bytes_cached[None],
        hist_ctr=state.hist_ctr[None],
        clock=state.clock[None], weights=state.weights[None],
        gds_L=state.gds_L[None], capacity_blocks=state.capacity_blocks[None],
        tenant_bytes=state.tenant_bytes[None],
        tenant_budget=state.tenant_budget[None],
        l0_epoch=state.l0_epoch[None])
    stats = jax.tree.map(lambda x: x[None], stats)
    return state, stats, n_evict[None], freed.astype(I32)[None]


@functools.lru_cache(maxsize=32)
def _drain_fn(mesh: Mesh, local_cfg: CacheConfig, batch: int):
    def run(state, stats):
        spec_state = jax.tree.map(lambda _: P(AXIS), state)
        spec_stats = jax.tree.map(lambda _: P(AXIS), stats)
        fn = jax.shard_map(
            functools.partial(_drain_shard, local_cfg, batch), mesh=mesh,
            in_specs=(spec_state, spec_stats),
            out_specs=(spec_state, spec_stats, P(AXIS), P(AXIS)),
            check_vma=False)
        return fn(state, stats)
    return jax.jit(run)


def _measured_migration_bytes(before, after) -> int:
    """Bytes that crossed a shard boundary: live keys present after the
    resize on a shard where they did not live before (real state delta)."""
    n_shards, value_words = before["shards"], before["value_words"]
    key_b, size_b = before["key"], before["size"]
    key_a, size_a = np.asarray(after.state.key), np.asarray(after.state.size)
    local = key_b.shape[0] // n_shards
    shard_of = np.arange(key_b.shape[0]) // local
    live_b = (size_b != SIZE_EMPTY) & (size_b != SIZE_HISTORY)
    live_a = (size_a != SIZE_EMPTY) & (size_a != SIZE_HISTORY)
    # A hot key may legitimately live on several shards at once (primary
    # plus write-through replica mirrors, DESIGN.md §14), so home must be
    # a set per key — counting a standing replica as a move would charge
    # phantom migration to every resize.
    home: dict = {}
    for k, s in zip(key_b[live_b], shard_of[live_b]):
        home.setdefault(int(k), set()).add(int(s))
    moved = 0
    for k, s, sz in zip(key_a[live_a], shard_of[live_a], size_a[live_a]):
        if int(k) in home and int(s) not in home[int(k)]:
            moved += int(sz) * 64 + 4 * value_words
    return moved


def _snapshot(dm, n_shards: int, value_words: int):
    return dict(key=np.asarray(dm.state.key).copy(),
                size=np.asarray(dm.state.size).copy(),
                shards=n_shards, value_words=value_words)


def resize_memory(mesh: Mesh, local_cfg: CacheConfig, dm,
                  new_global_capacity: int, *, drain: bool = True,
                  batch_per_shard: int = 64, max_steps: int = 256,
                  ) -> Tuple["DMCache", ResizeReport]:
    """Online memory resize (budget in 64B blocks): grow = scalar write
    (zero migration); shrink = scalar write + bounded priority-ordered
    drain until every shard's *byte* occupancy meets the new budget.

    Returns the resized cache and a report with measured state deltas.
    Raises RuntimeError if the drain cannot reach capacity in `max_steps`
    batches (so callers see a stuck drain instead of a silent overrun).
    """
    n_shards = mesh.shape[AXIS]
    assert new_global_capacity % n_shards == 0
    before = _snapshot(dm, n_shards, local_cfg.value_words)
    dm = _set_capacity_impl(dm, new_global_capacity, n_shards)

    steps = drained = freed = 0
    if drain:
        fn = _drain_fn(mesh, local_cfg, batch_per_shard)
        cap_per_shard = new_global_capacity // n_shards
        while (np.asarray(dm.state.bytes_cached) > cap_per_shard).any():
            if steps >= max_steps:
                raise RuntimeError(
                    f"shrink drain did not reach capacity={new_global_capacity}"
                    f" blocks in {max_steps} steps (bytes_cached="
                    f"{int(np.asarray(dm.state.bytes_cached).sum())})")
            state, stats, n_ev, n_freed = fn(dm.state, dm.stats)
            dm = dm._replace(state=state, stats=stats)
            drained += int(np.asarray(n_ev).sum())
            freed += int(np.asarray(n_freed).sum())
            steps += 1

    report = ResizeReport(
        migration_bytes=_measured_migration_bytes(before, dm),
        drained_objects=drained, drained_bytes=freed * 64,
        drain_steps=steps)
    return dm, report


def enforce_budget(mesh: Mesh, local_cfg: CacheConfig, dm, *,
                   batch_per_shard: int = 64, max_steps: int = 8,
                   ) -> Tuple["DMCache", int]:
    """Maintenance sweep: drain any shard over its byte budget.

    The batched access path tolerates transient occupancy drift (duplicate
    victims, hit-only steps, samples landing on empty slots at low live
    density — see DESIGN.md §8), and after a deep shrink the sampler alone
    may not hold the line. The memory-pool controller periodically runs
    this bounded drain to re-establish the budget. Returns (dm, drained).
    """
    drained = 0
    fn = _drain_fn(mesh, local_cfg, batch_per_shard)
    for _ in range(max_steps):
        nc = np.asarray(dm.state.bytes_cached)
        cap = np.asarray(dm.state.capacity_blocks)
        if not (nc > cap).any():
            break
        state, stats, n_ev, _ = fn(dm.state, dm.stats)
        dm = dm._replace(state=state, stats=stats)
        drained += int(np.asarray(n_ev).sum())
    return dm, drained


# ----------------------------------------------------------------------
# Compute scale: client-lane width changes with state carry-over.
# ----------------------------------------------------------------------

def resize_lanes(mesh: Mesh, local_cfg: CacheConfig, dm,
                 new_lanes_per_shard: int, *, seed: int = 1,
                 ) -> Tuple["DMCache", ResizeReport]:
    """Change the client-lane count per shard without touching the pool.

    Surviving lanes carry their FC cache and penalty buffers over.
    Decommissioned lanes flush: buffered freq deltas land in the table
    (the shutdown FAA burst) and pending expert penalties fold into the
    global weights (one last lazy-weight-update RPC). New lanes start
    from the current global weights with an empty FC cache.
    """
    n_shards = mesh.shape[AXIS]
    # Each shard holds lanes_per_shard lanes for every source shard
    # (repro.dm.sharded_cache._positions): blocks of (shard, source).
    blocks = n_shards * n_shards
    old_lanes = dm.clients.fc_slot.shape[0] // blocks
    new_total = blocks * new_lanes_per_shard
    if new_lanes_per_shard == old_lanes:
        return dm, ResizeReport(0, 0, 0, 0)
    before = _snapshot(dm, n_shards, local_cfg.value_words)

    local_slots = local_cfg.n_slots
    cl = jax.tree.map(np.asarray, dm.clients)
    per_block = jax.tree.map(
        lambda x: x.reshape((blocks, old_lanes) + x.shape[1:]), cl)

    freq = np.asarray(dm.state.freq).copy()
    weights = np.asarray(dm.state.weights).copy()     # [n_shards, E]
    keep = min(old_lanes, new_lanes_per_shard)

    if new_lanes_per_shard < old_lanes:
        # --- decommission flush (lanes [keep:] of every block) ----------
        # Penalty buffers are [E] classic / [T, E] per-tenant; the fold
        # below is shape-generic (each expert row normalizes on axis -1).
        pen_total = np.zeros(per_block.penalty_acc.shape[2:], np.float32)
        for b in range(blocks):
            fs = per_block.fc_slot[b, keep:].reshape(-1)
            fd = per_block.fc_delta[b, keep:].reshape(-1)
            ok = (fs >= 0) & (fs < local_slots)
            np.add.at(freq, b // n_shards * local_slots + fs[ok], fd[ok])
            pen_total += per_block.penalty_acc[b, keep:].sum(axis=0)
        lam = np.float32(local_cfg.learning_rate)
        w = weights[0] * np.exp(-lam * pen_total)
        w = np.maximum(
            w / np.maximum(w.sum(axis=-1, keepdims=True), 1e-30), 1e-4)
        weights = np.broadcast_to(w, weights.shape).copy()

    fresh = jax.tree.map(
        lambda x: x.reshape((blocks, new_lanes_per_shard) + x.shape[1:]),
        jax.tree.map(np.asarray,
                     init_clients(local_cfg, new_total, seed)))

    def merge(old, new):
        out = np.array(new)
        out[:, :keep] = old[:, :keep]
        return out.reshape((new_total,) + out.shape[2:])
    merged = jax.tree.map(merge, per_block, fresh)
    # New lanes adopt their shard's (post-flush) global weights ([E] or
    # [T, E]).
    wtail = per_block.local_weights.shape[2:]
    lw = merged.local_weights.reshape(
        (blocks, new_lanes_per_shard) + wtail)
    lw[:, keep:] = np.repeat(weights, n_shards, axis=0)[:, None]
    merged = merged._replace(
        local_weights=lw.reshape((new_total,) + wtail))

    sh = NamedSharding(mesh, P(AXIS))
    clients = jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), sh),
                           merged)
    state = dm.state._replace(
        freq=jax.device_put(jnp.asarray(freq), dm.state.freq.sharding),
        weights=jax.device_put(jnp.asarray(weights),
                               dm.state.weights.sharding))
    dm = dm._replace(state=state, clients=clients)
    return dm, ResizeReport(
        migration_bytes=_measured_migration_bytes(before, dm),
        drained_objects=0, drained_bytes=0, drain_steps=0)


# ----------------------------------------------------------------------
# Shard failure + recovery rewarm (DESIGN.md §14).
# ----------------------------------------------------------------------

def _put_like(arr, host):
    return jax.device_put(jnp.asarray(host), arr.sharding)


def _donate() -> bool:
    """Donate the pool through the on-device edits on an accelerator (a
    no-op warning on the CPU), as ``execute`` does."""
    return jax.default_backend() != "cpu"


@functools.lru_cache(maxsize=32)
def _wipe_fn(mesh: Mesh, donate: bool):
    """Jitted in-place wipe of one shard's slot arrays and occupancy rows,
    run where the pool lives: no column leaves its chip."""
    n_shards = mesh.shape[AXIS]

    def wipe(state, k):
        def own(x, n_rows):
            rows = jnp.arange(x.shape[0]) // (x.shape[0] // n_rows) == k
            return rows.reshape((-1,) + (1,) * (x.ndim - 1))

        def clear(x, n_rows, fill=0):
            return jnp.where(own(x, n_rows), jnp.asarray(fill, x.dtype), x)
        out = {name: clear(getattr(state, name), n_shards)
               for name in ("key", "key_hash", "ptr", "insert_ts",
                            "last_ts", "freq", "tenant", "ext", "values",
                            "n_cached", "bytes_cached", "tenant_bytes",
                            "hist_ctr", "gds_L")}
        out["size"] = clear(state.size, n_shards, SIZE_EMPTY)
        # Global L0 flush (DESIGN.md §15): the wipe — and the re-routing
        # that follows — happens outside access_group's version bumps,
        # and after failover the same key may be served by a different
        # shard's lanes, so EVERY shard's epoch advances to drop all
        # near-cache copies.  bucket_ver stays as-is (monotone): pre-wipe
        # tokens can then never revalidate against the rebuilt table.
        out["l0_epoch"] = state.l0_epoch + 1
        return state._replace(**out)
    return jax.jit(wipe, donate_argnums=(0,) if donate else (),
                   out_shardings=NamedSharding(mesh, P(AXIS)))


def fail_wipe_shard(mesh: Mesh, local_cfg: CacheConfig, dm, k: int):
    """Ground-truth shard loss: shard k's DRAM is gone.  Zeroes its slot
    arrays and per-shard occupancy counters in place, on the chips.
    Control-plane scalars — the logical clock and the expert weights —
    survive: the replacement node re-syncs them on join, and keeping the
    clock in lockstep is what makes post-recovery decisions
    deterministic across reruns."""
    n_shards = mesh.shape[AXIS]
    assert 0 <= k < n_shards
    state = _wipe_fn(mesh, _donate())(dm.state, jnp.int32(k))
    return dm._replace(state=state)


@functools.lru_cache(maxsize=32)
def _move_records_fn(mesh: Mesh, donate: bool):
    """Jitted copy of records between shards: ``values[dst] = values[src]``
    for every pair with ``dst >= 0``.  Each shard sends the rows it owns
    (one ``psum`` of the moved rows alone); the destination shard writes
    them.  No shard's column leaves its chip."""
    def move(values, src, dst):
        me = jax.lax.axis_index(AXIS)
        n = values.shape[0]
        mine = src // n == me
        rows = jnp.where(mine[:, None],
                         values[jnp.where(mine, src - me * n, 0)], 0)
        rows = jax.lax.psum(rows, AXIS)
        at = jnp.where((dst >= 0) & (dst // n == me), dst - me * n, n)
        return values.at[at].set(rows, mode="drop")
    return jax.jit(jax.shard_map(move, mesh=mesh,
                                 in_specs=(P(AXIS), P(), P()),
                                 out_specs=P(AXIS), check_vma=False),
                   donate_argnums=(0,) if donate else ())


def rewarm_shard(mesh: Mesh, local_cfg: CacheConfig, dm, k: int, *,
                 max_objects: int = 512) -> Tuple["DMCache", ResizeReport]:
    """Recovery drain: rewarm a rejoined shard from the survivors.

    While shard k was out, requests for its buckets re-routed to survivor
    shards (`Cluster.membership` rendezvous), which absorbed k's working
    set into their own tables.  On rejoin those objects would sit cold on
    the survivors while k re-misses everything; this bounded host-side
    drain moves the hottest survivor-held objects whose home bucket
    belongs to k back onto k — hottest-first by frequency, respecting
    k's byte capacity and per-tenant budgets, each move clearing the
    survivor's slot.  Reported ``migration_bytes`` uses the same
    ``size*64 + value`` formula as `_measured_migration_bytes` (these
    moves are real cross-shard traffic, unlike a capacity resize)."""
    n_shards = mesh.shape[AXIS]
    assert 0 <= k < n_shards
    ls, lb, A = local_cfg.n_slots, local_cfg.n_buckets, local_cfg.assoc
    st = dm.state
    # Records stay on their chips (_move_records_fn); the metadata
    # columns are planned here.
    names = ("key", "key_hash", "size", "ptr", "insert_ts", "last_ts",
             "freq", "ext", "tenant")
    arr = {n: np.array(getattr(st, n)) for n in names}
    kh = np.asarray(hash_key(jnp.asarray(arr["key"])))
    home = np.asarray(bucket_of(jnp.asarray(kh), lb * n_shards)) // lb
    local_bkt = np.asarray(bucket_of(jnp.asarray(kh), lb))
    slot_shard = np.arange(arr["key"].shape[0]) // ls
    live = (arr["size"] != SIZE_EMPTY) & (arr["size"] != SIZE_HISTORY)
    cand = np.nonzero(live & (slot_shard != k) & (home == k))[0]
    if cand.size == 0:
        return dm, ResizeReport(0, 0, 0, 0)
    cand = cand[np.argsort(-arr["freq"][cand].astype(np.int64),
                           kind="stable")][:max_objects]

    nc = np.array(st.n_cached)
    bc = np.array(st.bytes_cached)
    tb = np.array(st.tenant_bytes)
    tbud = np.array(st.tenant_budget)
    cap_k = int(np.array(st.capacity_blocks)[k])
    multi = local_cfg.n_tenants > 1
    moved = moved_bytes = freed_blocks = 0
    srcs = np.zeros(max_objects, np.int32)
    dsts = np.full(max_objects, -1, np.int32)
    for s_idx in cand:
        sz = int(arr["size"][s_idx])
        if bc[k] + sz > cap_k:
            continue
        t = int(arr["tenant"][s_idx])
        if multi and tb[k, t] + sz > tbud[k, t]:
            continue
        base = k * ls + int(local_bkt[s_idx]) * A
        free = np.nonzero(arr["size"][base:base + A] == SIZE_EMPTY)[0]
        if free.size == 0:
            continue
        dst = base + int(free[0])
        for n in names:
            arr[n][dst] = arr[n][s_idx]
        srcs[moved], dsts[moved] = s_idx, dst
        src = int(slot_shard[s_idx])
        arr["key"][s_idx] = 0
        arr["key_hash"][s_idx] = 0
        arr["size"][s_idx] = SIZE_EMPTY
        arr["ptr"][s_idx] = 0
        nc[k] += 1
        nc[src] -= 1
        bc[k] += sz
        bc[src] -= sz
        tb[k, t] += sz
        tb[src, t] -= sz
        moved += 1
        freed_blocks += sz
        moved_bytes += sz * 64 + 4 * local_cfg.value_words
    out = {n: _put_like(getattr(st, n), arr[n]) for n in names}
    out["values"] = _move_records_fn(mesh, _donate())(
        st.values, jnp.asarray(srcs), jnp.asarray(dsts))
    out["n_cached"] = _put_like(st.n_cached, nc)
    out["bytes_cached"] = _put_like(st.bytes_cached, bc)
    out["tenant_bytes"] = _put_like(st.tenant_bytes, tb)
    if moved:
        # Rewarm moves objects between shards without touching bucket
        # versions — flush every lane's L0 via the epoch (DESIGN.md §15)
        # so a survivor-filled near-cache copy can't outlive the move.
        out["l0_epoch"] = _put_like(st.l0_epoch, np.array(st.l0_epoch) + 1)
    dm = dm._replace(state=st._replace(**out))
    return dm, ResizeReport(
        migration_bytes=moved_bytes, drained_objects=moved,
        drained_bytes=freed_blocks * 64, drain_steps=1 if moved else 0)
