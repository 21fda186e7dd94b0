"""The Ditto cache: client-centric caching framework + distributed adaptive
caching, as one batched functional step.

Concurrency model: one step applies a *group* of client operations — a
[G, C] block of G rounds x C client lanes (G=1 recovers the paper's
one-op-per-client-thread step).  All wide-path reads (bucket probe,
sampling) observe the step-entry snapshot; updates are applied with
deterministic combines in the order (metadata updates → evictions →
inserts), the batched analogue of the paper's CAS/FAA-mediated races.
Per-request logical timestamps (``clock + round``) drive every
time-dependent decision — metadata, priorities, rng streams — so a
group executes exactly as its rounds would sequentially whenever the
rounds touch disjoint buckets (the planner's grouping invariant; see
``workloads/plan.py`` and DESIGN.md §9).  The narrow per-lane state
(frequency-counter cache, expert weights / lazy sync) threads through
the rounds in order, so it is sequential by construction.

Every operation is also metered in "issued remote ops" (OpStats) — the
RDMA-verb counts of the paper's cost model — so the efficiency/ablation
benchmarks (Figs. 2/14/24/25) are driven by real counters from this
implementation, not hand-derived formulas.

When ``cfg.l0_entries > 0`` each client lane additionally runs a tiny
near-cache (L0) probed before any remote work (step 1a): valid read
hits are served lane-locally and masked out of the step, moving zero
RDMA counters.  Coherence is per-bucket version tokens plus a
structural epoch — see DESIGN.md §15.  ``l0_entries=0`` (default)
compiles the tier away entirely; the step is bit-identical to a build
without it.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import priority as prio
from repro.core.fc_cache import fc_access, fc_access_group
from repro.core.hashing import bucket_of, hash_key
from repro.core.types import (SIZE_EMPTY, SIZE_HISTORY, CacheConfig,
                              CacheState, ClientState, MDView, OpStats,
                              init_cache, init_clients, init_stats, stats_add)
from repro.kernels import ops as kops

I32 = jnp.int32
U32 = jnp.uint32
F32 = jnp.float32


class AccessResult(NamedTuple):
    hit: jnp.ndarray       # bool[G, C]
    value: jnp.ndarray     # u32[G, C, W] (garbage where miss)
    evicted: jnp.ndarray   # bool[G, C] — this op performed a global eviction
    regret: jnp.ndarray    # bool[G, C]


def _md_view(state: CacheState, idx: jnp.ndarray,
             ts: jnp.ndarray | None = None) -> MDView:
    """Gather an MDView for slot indices (any shape).  ``ts`` is the
    per-op logical clock (broadcastable against idx); defaults to the
    state clock (G=1 semantics)."""
    size = state.size[idx].astype(F32)
    clock = state.clock if ts is None else ts
    return MDView(
        size=size,
        insert_ts=state.insert_ts[idx].astype(F32),
        last_ts=state.last_ts[idx].astype(F32),
        freq=state.freq[idx].astype(F32),
        ext=state.ext[idx],
        clock=clock.astype(F32),
        gds_L=state.gds_L,
        cost=jnp.ones_like(size),
    )


def _is_live(size: jnp.ndarray) -> jnp.ndarray:
    return (size != SIZE_EMPTY) & (size != SIZE_HISTORY)


def _hist_age(hist_ctr: jnp.ndarray, hist_id: jnp.ndarray) -> jnp.ndarray:
    """Logical-FIFO age with wrap-around (paper's 48-bit counter -> u32)."""
    return (hist_ctr - hist_id).astype(U32)


def _choose_expert(weights: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """Sample expert index ~ normalized weights (opportunistic eviction)."""
    p = weights / jnp.maximum(jnp.sum(weights, axis=-1, keepdims=True), 1e-30)
    cdf = jnp.cumsum(p, axis=-1)
    return jnp.sum((cdf < u[..., None]).astype(I32), axis=-1)


def apply_penalties(weights: jnp.ndarray, penalties: jnp.ndarray,
                    lam) -> jnp.ndarray:
    """Multiplicative-weights regret update, clamp-THEN-normalize.

    The single ordering shared by the core path and the DM weight-sync
    RPC (`dm/sharded_cache.py`): normalizing last guarantees the global
    weights always sum to exactly 1.  Shape-generic over leading axes
    (f32[E] classic, f32[T, E] per-tenant): each expert row normalizes
    independently."""
    w = weights * jnp.exp(-lam * penalties)
    w = jnp.maximum(w, 1e-4)
    return w / jnp.sum(w, axis=-1, keepdims=True)


def _first_winner(x: jnp.ndarray, valid: jnp.ndarray,
                  domain: int) -> jnp.ndarray:
    """bool[B]: True for the first occurrence of each distinct value of
    x in [0, domain) among valid lanes.  Scatter-min duplicate
    resolution (cheaper than a sort on CPU/TPU): the earliest flattened
    index — i.e. the earliest *round* — wins, matching sequential
    precedence."""
    B = x.shape[0]
    pos = jnp.arange(B, dtype=I32)
    tgt = jnp.where(valid, x.astype(I32), domain)
    best = jnp.full((domain + 1,), B, I32).at[tgt].min(pos)
    return valid & (best[jnp.where(valid, x.astype(I32), 0)] == pos)


def access_group(cfg: CacheConfig, state: CacheState, clients: ClientState,
                 stats: OpStats, keys: jnp.ndarray, *,
                 is_write: jnp.ndarray | None = None,
                 obj_size: jnp.ndarray | None = None,
                 values: jnp.ndarray | None = None,
                 tenant: jnp.ndarray | None = None,
                 insert_on_miss: bool = True,
                 shadow: jnp.ndarray | None = None,
                 ) -> Tuple[CacheState, ClientState, OpStats, AccessResult]:
    """One batched cache step over a [G, C] request group.

    Executes G rounds of C concurrent client ops as ONE widened step:
    probe, hit-metadata update, inserts and the sampled eviction run
    vmapped across all G*C requests against the step-entry snapshot,
    while the per-lane FC cache and expert-weight state thread through
    the rounds sequentially.  Round r runs at logical time clock+r: its
    timestamps, priorities and rng draws are identical to what a
    sequential execution of the rounds would produce, which makes the
    batched step decision-equivalent to the sequential one whenever the
    rounds are bucket-disjoint (``workloads.plan``).

    Its stages run under named scopes that a profiler trace carries as
    each operation's ``op_name`` (DESIGN.md §13): ``ditto.probe`` (steps
    1a-1), ``ditto.hit_update`` (2-3), ``ditto.evict`` (4-5b),
    ``ditto.apply`` (6-6b) and ``ditto.account`` (7).  They add metadata
    only, no operation.

    Args:
      keys: u32[G, C]; 0 marks a padded no-op lane.
      is_write: bool[G, C] — SET ops (value update; costed as the Set path).
      obj_size: u32[G, C] object size in 64B blocks (default 1).
      values: u32[G, C, W] payload written on insert/set.
      tenant: u32[G, C] tenant id per request in [0, n_tenants); ignored
        (and the per-slot tenant column left untouched) when
        cfg.n_tenants == 1, so single-tenant behavior is bit-identical
        to the pre-tenant engine.
      shadow: bool[G, C] — write-through replica mirrors (DM layer).
        Shadow ops execute fully (state mutation, RDMA/wire counters)
        but are excluded from the client-visible counters
        (gets/sets/hits/misses/hit_bytes/miss_bytes) and tallied in
        ``replica_writes`` instead, so hit ratios keep the offered-load
        denominator.  ``None`` is bit-identical to all-False.
    """
    G, C = keys.shape
    B = G * C
    E = cfg.n_experts
    K = cfg.n_samples
    A = cfg.assoc
    Tn = cfg.n_tenants
    multi = Tn > 1
    names = cfg.experts
    adaptive = E > 1
    fused = cfg.backend == "fused"
    if fused:
        unsupported = [n for n in names if n not in kops.KERNEL_EXPERTS]
        if unsupported:
            raise ValueError(
                f"backend='fused' supports experts {kops.KERNEL_EXPERTS}; "
                f"got {unsupported} (use backend='reference')")
        if cfg.n_slots > kops.FUSED_MAX_SLOTS or cfg.n_slots % 128:
            raise ValueError(
                f"backend='fused' keeps the slot tables in VMEM: it needs "
                f"n_slots a multiple of 128 and at most FUSED_MAX_SLOTS="
                f"{kops.FUSED_MAX_SLOTS} (got n_slots={cfg.n_slots}; use "
                "backend='reference')")

    with jax.named_scope("ditto.probe"):
        if is_write is None:
            is_write = jnp.zeros((G, C), bool)
        if obj_size is None:
            obj_size = jnp.ones((G, C), U32)
        if values is None:
            values = jnp.zeros((G, C, cfg.value_words), U32)
        if tenant is None:
            tenant = jnp.zeros((G, C), U32)

        keys_b = keys.reshape(B)
        op = keys_b != 0
        is_write = is_write.reshape(B)
        obj_size = jnp.clip(obj_size.reshape(B), 1, SIZE_HISTORY - 1).astype(U32)
        values = values.reshape(B, cfg.value_words)
        tenant_b = jnp.minimum(tenant.reshape(B).astype(U32), U32(Tn - 1))

        clock = state.clock
        n_slots_total = cfg.n_slots
        # Per-request logical timestamps: round r of the group runs at
        # clock + r, exactly as a sequential execution would.
        ts_round = clock + jnp.arange(G, dtype=U32)                    # [G]
        ts_req = jnp.repeat(ts_round, C)                               # [B]
        rng_b = jnp.tile(clients.rng, (G, 1))                          # [B, 2]
        step_rng = jax.vmap(jax.random.fold_in)(rng_b, ts_req)
        lane_b = jnp.tile(jnp.arange(C, dtype=I32), G)                 # [B]

        # ------------------------------------------------------------------
        # 1a. L0 near-cache probe (DESIGN.md §15): serve GETs from the
        #     per-lane near-cache before any remote machinery runs.  An
        #     entry is valid only while its captured bucket-version token
        #     still equals the owning bucket's current version AND the lane
        #     has observed the current flush epoch — any committed mutation
        #     of the bucket (or an out-of-band drain/failover) silently
        #     invalidates it, so an L0 hit can never serve a stale value.
        #     Requests served here are masked to padded no-op lanes (key 0):
        #     the entire remote path below — probe, metadata, inserts,
        #     eviction, RDMA/wire counters — sees them exactly as it sees
        #     padding, which is what makes the `l0_entries == 0` gate (zero
        #     added equations, untouched keys) bit-identical to the pre-L0
        #     engine.
        # ------------------------------------------------------------------
        l0 = cfg.l0_entries > 0
        if l0:
            shadow_b = (jnp.zeros((B,), bool) if shadow is None
                        else shadow.reshape(B))
            ent_bkt = jnp.clip(clients.l0_bkt, 0, cfg.n_buckets - 1)
            ent_present = clients.l0_key != 0                      # [C, L0]
            ent_valid = (ent_present
                         & (clients.l0_seen_epoch == state.l0_epoch)[:, None]
                         & (clients.l0_tok == state.bucket_ver[ent_bkt]))
            l0_stale = ent_present & ~ent_valid
            n_l0_inval = jnp.sum(l0_stale)
            l0_match = (ent_valid[lane_b]
                        & (clients.l0_key[lane_b] == keys_b[:, None]))  # [B, L0]
            l0_idx = jnp.argmax(l0_match, axis=1)                  # [B]
            # Only plain GETs are servable locally: writes (and replica
            # mirrors) must travel to the pool so they bump the bucket
            # version every other lane's entries validate against.
            l0_hit = jnp.any(l0_match, axis=1) & op & ~is_write & ~shadow_b
            l0_value = clients.l0_val[lane_b, l0_idx]              # [B, W]
            l0_size = clients.l0_sz[lane_b, l0_idx]                # [B]
            keys_b = jnp.where(l0_hit, U32(0), keys_b)
            op = keys_b != 0
            n_l0_hit = jnp.sum(l0_hit)

        # ------------------------------------------------------------------
        # 1. Bucket probe (1 RDMA_READ per op; with SFHT it carries metadata).
        #    fused: one Pallas pass does the bucket match + history match;
        #    the bucket gathers below are still needed by the insert path (4).
        # ------------------------------------------------------------------
        kh = hash_key(keys_b)
        bucket = bucket_of(kh, cfg.n_buckets)
        bslots = bucket[:, None] * A + jnp.arange(A)[None, :]          # [B, A]
        b_key = state.key[bslots]
        b_size = state.size[bslots]
        b_hash = state.key_hash[bslots]
        b_ptr = state.ptr[bslots]

        live = _is_live(b_size)
        is_hist = b_size == SIZE_HISTORY
        h_age = _hist_age(state.hist_ctr, b_ptr)
        h_valid = is_hist & (h_age < U32(cfg.history_len))

        if fused:
            found, slot, hist_found, hslot = kops.access_probe_op(
                state.key, state.size, state.key_hash, state.ptr, keys_b,
                state.hist_ctr, assoc=A, history_len=cfg.history_len)
            found = found & op
            hist_found = hist_found & op
            slot = jnp.where(found, slot, -1)
        else:
            match = live & (b_key == keys_b[:, None]) & op[:, None]
            found = jnp.any(match, axis=1)
            mslot = jnp.take_along_axis(
                bslots, jnp.argmax(match, axis=1)[:, None], axis=1)[:, 0]
            slot = jnp.where(found, mslot, -1)

            # History probe: same bucket read (embedded entries, §4.3.1).
            h_match = h_valid & (b_hash == kh[:, None]) & op[:, None]
            hist_found = jnp.any(h_match, axis=1) & ~found
            hslot = jnp.take_along_axis(
                bslots, jnp.argmax(h_match, axis=1)[:, None], axis=1)[:, 0]
        regret = hist_found & adaptive & cfg.use_lwh

        hit = found
        miss = op & ~found

    with jax.named_scope("ditto.hit_update"):
        # ------------------------------------------------------------------
        # 2. Metadata update on hits (stateless: one combined RDMA_WRITE with
        #    SFHT; stateful freq goes through the FC cache).  The FC cache
        #    processes the whole group at once — a lane's increments to the
        #    same entry combine before any remote FAA issues, the group-level
        #    generalization of the paper's client-side write combining (for
        #    G=1 this is exactly the sequential per-round path).
        #    fused: one Pallas pass applies last_ts/ext at hit slots + the
        #    combining freq FAA, at per-request timestamps.
        # ------------------------------------------------------------------
        slot_hit = jnp.where(hit, slot, -1)
        if G == 1:
            clients, em = fc_access(cfg, clients, slot_hit, clock)
            emit_slot, emit_delta = em.slot.reshape(-1), em.delta.reshape(-1)
            n_faa, n_fc_hit = em.n_faa, em.n_hit
        else:
            clients, emit_slot, emit_delta, n_faa, n_fc_hit = fc_access_group(
                cfg, clients, slot_hit.reshape(G, C), ts_round)
            emit_slot = emit_slot.reshape(-1)
            emit_delta = emit_delta.reshape(-1)

        upd_idx = jnp.where(hit, slot, n_slots_total)
        # Effective hit time per slot: the max request-ts among this group's
        # hits on it (all equal under the planner's grouping invariant; the
        # deterministic combine otherwise).  Shared by both backends.
        eff = jnp.zeros((n_slots_total + 1,), U32).at[upd_idx].max(ts_req)
        eff_op = eff[jnp.maximum(slot, 0)]                             # [B]
        if fused:
            freq, last_ts, ext = kops.hit_metadata_update_op(
                state.freq, state.last_ts, state.ext, slot_hit, ts_req,
                emit_slot, emit_delta)
        else:
            old_last = state.last_ts[jnp.maximum(slot, 0)]
            old_freq = state.freq[jnp.maximum(slot, 0)]
            new_ext = prio.update_ext(state.ext[jnp.maximum(slot, 0)],
                                      old_last, old_freq, eff_op)
            last_ts = state.last_ts.at[upd_idx].max(ts_req, mode="drop")
            ext = state.ext.at[upd_idx].set(new_ext, mode="drop")
            eidx = jnp.where(emit_slot >= 0, emit_slot, n_slots_total)
            freq = state.freq.at[eidx].add(emit_delta, mode="drop")
        # SETs overwrite payloads (last-writer-wins within the group); the
        # write itself is applied after the tenant budget gate (step 5b),
        # which may refuse a budget-breaking grow — all inputs here are the
        # step-entry snapshot, so deferring the scatter changes nothing.

        # ------------------------------------------------------------------
        # 3. Regret collection + lazy expert-weight update (§4.3.2).  The
        #    group's penalties aggregate into ONE multiplicative-weights
        #    update and one sync decision per lane per step — the batched
        #    analogue of the paper's locally-buffered penalties (for G=1
        #    this is exactly the per-round update).  Weights are per-tenant
        #    rows ([T, E], §11): every request's regret lands on its own
        #    tenant's row, so each tenant converges to its own best-fit
        #    expert.  The math below runs in canonical [C, T, E] space; for
        #    n_tenants == 1 the T axis is a length-1 broadcast and every
        #    reduction is elementwise-identical to the pre-tenant engine.
        # ------------------------------------------------------------------
        h_bmap = state.insert_ts[jnp.maximum(hslot, 0)]          # expert bitmap
        h_age_sel = _hist_age(state.hist_ctr, state.ptr[jnp.maximum(hslot, 0)])
        d = jnp.float32(cfg.discount)
        pen = jnp.power(d, h_age_sel.astype(F32))                # d^t
        bits = ((h_bmap[:, None] >> jnp.arange(E)[None, :]) & 1).astype(F32)
        pen_e = jnp.where(regret[:, None], pen[:, None] * bits, 0.0)   # [B, E]
        # One scatter-add over the B requests replaces the per-tenant masked
        # reductions (the old `for t in range(Tn)` stack traced O(Tn) full-
        # width reductions; updates apply in request = round order, so the
        # G=1 and single-tenant results are element-identical).
        tb_i = tenant_b.astype(I32)
        pen_lane = jnp.zeros((C, Tn, E), F32).at[lane_b, tb_i].add(
            pen_e)                                               # [C, T, E]
        reg_lane = jnp.zeros((C, Tn), I32).at[lane_b, tb_i].add(
            regret.astype(I32))                                  # [C, T]

        # One threefry draw per request covers both the expert choice and the
        # sampling offset (step_rng is already a per-request folded stream).
        u2 = jax.vmap(lambda r: jax.random.uniform(r, (2,)))(step_rng)
        u_exp = u2[:, 0]

        lam = jnp.float32(cfg.learning_rate)
        lw3 = clients.local_weights if multi else clients.local_weights[:, None]
        pacc3 = clients.penalty_acc if multi else clients.penalty_acc[:, None]
        pcnt2 = clients.penalty_cnt if multi else clients.penalty_cnt[:, None]
        w2 = state.weights if multi else state.weights[None]     # [T, E]
        local_w = lw3 * jnp.exp(-lam * pen_lane)
        pacc = pacc3 + pen_lane
        pcnt = pcnt2 + reg_lane.astype(I32)

        if cfg.use_lwu:
            syncing = pcnt >= cfg.sync_period                    # [C, T]
        else:
            syncing = reg_lane > 0  # eager: RPC on every regret
        tot_pen = jnp.sum(jnp.where(syncing[..., None], pacc, 0.0),
                          axis=0)                                # [T, E]
        gw = apply_penalties(w2, tot_pen, lam)                   # [T, E]
        local_w = jnp.where(syncing[..., None], gw[None], local_w)
        local_w = jnp.maximum(local_w, 1e-4)
        pacc = jnp.where(syncing[..., None], 0.0, pacc)
        pcnt = jnp.where(syncing, 0, pcnt)
        n_sync = jnp.sum(syncing).astype(I32)
        e_choice = _choose_expert(local_w[lane_b, tb_i], u_exp)  # [B]

    with jax.named_scope("ditto.evict"):
        # ------------------------------------------------------------------
        # 4. Inserts: read-through on miss. One insert per (key, bucket) per
        #    step; duplicate keys / bucket collisions retry on a later access.
        # ------------------------------------------------------------------
        want_insert = miss & (insert_on_miss | is_write)
        # First-of-bucket dedup: duplicate keys share a bucket, so the first
        # inserting op per bucket is also the first per key.
        winner = _first_winner(bucket, want_insert, cfg.n_buckets)
        dropped = want_insert & ~winner

        free = (b_size == SIZE_EMPTY) | (is_hist & ~h_valid)     # [B, A]
        has_free = jnp.any(free, axis=1)
        free_slot = jnp.take_along_axis(
            bslots, jnp.argmax(free, axis=1)[:, None], axis=1)[:, 0]

        # Bucket-local fallback eviction when the bucket is full: overwrite the
        # oldest *valid* history entry first, else the lowest-priority live
        # object under this client's sampled expert (counted separately).
        b_md = _md_view(state, bslots, ts_req[:, None])
        b_prio = prio.priorities(b_md, names)                     # [B, A, E]
        b_prio_e = jnp.take_along_axis(
            b_prio, e_choice[:, None, None], axis=2)[:, :, 0]     # [B, A]
        b_prio_e = jnp.where(live, b_prio_e, jnp.inf)
        fb_obj_slot = jnp.take_along_axis(
            bslots, jnp.argmin(b_prio_e, axis=1)[:, None], axis=1)[:, 0]
        hist_age_in_bucket = jnp.where(h_valid, h_age.astype(F32), -jnp.inf)
        fb_hist_slot = jnp.take_along_axis(
            bslots, jnp.argmax(hist_age_in_bucket, axis=1)[:, None], axis=1)[:, 0]
        has_valid_hist = jnp.any(h_valid, axis=1)
        has_live = jnp.any(live, axis=1)

        fallback_hist = winner & ~has_free & has_valid_hist
        fallback_obj = winner & ~has_free & ~has_valid_hist & has_live
        plain = winner & has_free
        ins_ok = plain | fallback_hist | fallback_obj
        ins_slot = jnp.where(plain, free_slot,
                             jnp.where(fallback_hist, fb_hist_slot, fb_obj_slot))
        dropped = dropped | (winner & ~ins_ok)

        # ------------------------------------------------------------------
        # 5. Global sampled eviction (the paper's core): when over the byte
        #    budget, each capacity-consuming insert samples K slots, evaluates
        #    all E expert priorities, and evicts from its chosen expert's
        #    priority ranking.  The pool is a BYTE budget (64B blocks): an
        #    insert charges its object size and evictions credit the victim's
        #    size, so the over-capacity catch-up quota is a per-op *block*
        #    deficit — each evicting op peels ranked victims (lowest priority
        #    first, up to K) until the freed blocks cover its share.  With
        #    uniform 1-block objects this degenerates exactly to the old
        #    object-count quota.
        # ------------------------------------------------------------------
        consumes = plain | fallback_hist                          # +1 live object
        # SETs that re-size an existing object charge (or credit) the byte
        # delta vs the stored size, and *growing* SETs join the evictor set —
        # otherwise hit-only write traffic could inflate objects past the
        # budget with nothing ever sampling a victim. Uniform 1-block
        # workloads have zero delta, recovering the old behavior exactly.
        old_sz = state.size[jnp.maximum(slot, 0)]
        set_growth = jnp.where(hit & is_write,
                               obj_size.astype(I32) - old_sz.astype(I32), 0)
        growing_set = hit & is_write & (set_growth > 0)
        chargers = consumes | growing_set
        n_charge = jnp.sum(chargers).astype(I32)
        inc_blocks = (jnp.sum(jnp.where(consumes, obj_size, U32(0))).astype(I32)
                      + jnp.sum(set_growth))
        over = state.bytes_cached + inc_blocks - state.capacity_blocks
        # Per-op victim quota in blocks: each evicting op must free (at least)
        # its ceil-share of the block deficit, bounded by K victims.
        quota = jnp.where(
            over <= 0, 0,
            jnp.maximum((over + jnp.maximum(n_charge, 1) - 1)
                        // jnp.maximum(n_charge, 1), 1))

        # Tenant-scoped budget enforcement (§11): an over-budget tenant's
        # chargers must peel victims from the tenant's OWN slots (the sample
        # filter below), with a quota that is their ceil-share of the
        # *tenant's* byte deficit; under-budget tenants fall back to the
        # shared-pool ranking and the global quota (work conservation).  For
        # n_tenants == 1 the single tenant's budget IS capacity_blocks and
        # every per-tenant quantity collapses to the global ones above, so
        # the classic engine skips the whole pipeline (identical decisions,
        # zero extra work on the gated hot path).
        if multi:
            occ_t = state.tenant_bytes
            bud_t = state.tenant_budget
            charge_d = (jnp.where(consumes, obj_size.astype(I32), 0)
                        + jnp.where(hit & is_write, set_growth, 0))  # [B]
            # Integer scatter-adds over the tenant ids: exact (order-free)
            # replacements for the old per-tenant masked reductions.
            inc_t = jnp.zeros((Tn,), I32).at[tb_i].add(charge_d)     # [T]
            n_charge_t = jnp.zeros((Tn,), I32).at[tb_i].add(
                chargers.astype(I32))                                # [T]
            over_t = occ_t + inc_t - bud_t                       # [T]
            quota_t = jnp.where(
                over_t <= 0, 0,
                jnp.maximum((over_t + jnp.maximum(n_charge_t, 1) - 1)
                            // jnp.maximum(n_charge_t, 1), 1))
            scoped = chargers & (over_t[tenant_b] > 0)           # [B]
            must_evict = scoped | (chargers & (over > 0))
            quota_b = jnp.where(scoped, quota_t[tenant_b], quota)  # [B]
            tfilt = jnp.where(scoped, tenant_b.astype(I32), -1)  # [B]
        else:
            must_evict = chargers & (over > 0)
            quota_b = quota          # scalar; broadcasts in both engines
            tfilt = None             # no tenant filter (kernel fills -1)

        # Contiguous-window sampling (§4.2.1): ONE read of W consecutive slots
        # from a random offset; the first K live objects in the window are the
        # sample. (This is also the TPU-friendly layout: one dense tile.)
        # fused: the whole decision — window gather, E expert priorities,
        # chosen-expert ranking, per-op quota — is one Pallas call over
        # wrap-padded metadata columns; victims come back as [B, K].
        W = cfg.sample_window or 4 * K
        offs = jnp.minimum((u2[:, 1] * cfg.n_slots).astype(I32),
                           cfg.n_slots - 1)
        if fused:
            victims_2d, cand_slot = kops.ranked_eviction_op(
                state.size, state.insert_ts, state.last_ts, state.freq, offs,
                e_choice, must_evict, quota_b, ts_req,
                tenant=state.tenant if multi else None, tfilt=tfilt,
                window=W, k=K, experts=names)                     # [B, K], [B, E]
            take = victims_2d >= 0
        else:
            samp = (offs[:, None] + jnp.arange(W)[None, :]) % cfg.n_slots  # [B, W]
            s_md = _md_view(state, samp, ts_req[:, None])
            s_live_raw = _is_live(state.size[samp])
            if multi:
                # Tenant filter: a budget-scoped op samples only its own
                # tenant's live objects (the first K of them in the window).
                s_ten = state.tenant[samp].astype(I32)
                s_elig = s_live_raw & ((tfilt[:, None] < 0)
                                       | (s_ten == tfilt[:, None]))
            else:
                s_elig = s_live_raw
            in_sample = s_elig & (jnp.cumsum(s_elig, axis=1) <= K)
            s_live = in_sample
            s_prio = prio.priorities(s_md, names)                 # [B, W, E]
            s_prio = jnp.where(s_live[:, :, None], s_prio, jnp.inf)
            cand_k = jnp.argmin(s_prio, axis=1)                   # [B, E]
            cand_slot = jnp.take_along_axis(samp, cand_k, axis=1)  # [B, E]

            # Chosen expert's priority ranking over this op's samples:
            # peel off the lowest-priority sample until the freed blocks
            # cover the op's quota (== the shortest prefix of a stable sort
            # whose sizes sum past the deficit; the exact mirror of the
            # fused kernel's loop, and far cheaper than an argsort on CPU).
            prio_e = jnp.take_along_axis(
                s_prio, e_choice[:, None, None], axis=2)[:, :, 0]  # [B, W]
            s_blocks = jnp.where(s_live, s_md.size, 0.0)          # [B, W]
            cols = jnp.arange(W)[None, :]
            vs = []
            freed = jnp.zeros((B,), F32)
            for j in range(K):
                arg = jnp.argmin(prio_e, axis=1)                  # [B]
                val = jnp.take_along_axis(prio_e, arg[:, None], axis=1)[:, 0]
                ok = (freed < quota_b.astype(F32)) & (val < jnp.inf) & must_evict
                vs.append(jnp.where(ok, jnp.take_along_axis(
                    samp, arg[:, None], axis=1)[:, 0], -1))
                freed = freed + jnp.where(ok, jnp.take_along_axis(
                    s_blocks, arg[:, None], axis=1)[:, 0], 0.0)
                prio_e = jnp.where(cols == arg[:, None], jnp.inf, prio_e)
            victims_2d = jnp.stack(vs, axis=1)                    # [B, K]
            take = victims_2d >= 0
        V = victims_2d.shape[1]  # K on both paths (at most K victims per op
        # regardless of the block quota), so the reference and fused rankings
        # coincide rank for rank.
        victims = victims_2d.reshape(-1)                          # [B*V]
        ev_winner = _first_winner(victims, victims >= 0, n_slots_total)
        n_evict = jnp.sum(ev_winner).astype(I32)
        evicting = must_evict & jnp.any(take, axis=1)

        # ------------------------------------------------------------------
        # 5b. Tenant budget gate (multi-tenant only, §11): the sampled
        #     eviction is best-effort — a window holding too few of the
        #     tenant's objects frees fewer blocks than the deficit demands —
        #     so capacity charges (inserts at obj_size, SET re-sizes at
        #     their byte delta, shrinks crediting) are admitted against the
        #     tenant's *post-eviction* allowance as a round-ordered prefix;
        #     the excess inserts and growing SETs are refused (counted in
        #     insert_drops; a refused grow keeps the object's old size and
        #     payload, like a failed remote write).  Prefix admission is
        #     conservative — a refused charge still occupies its slot in
        #     the running sum — which is what makes per-tenant budgets a
        #     hard isolation guarantee instead of a drifting target.
        #     Single-tenant configs skip the gate entirely (the classic
        #     engine tolerates transient overshoot; see DESIGN.md §8).
        # ------------------------------------------------------------------
        if multi:
            v_idx = jnp.maximum(victims, 0)
            v_ten = jnp.where(ev_winner, state.tenant[v_idx].astype(I32), 0)
            v_sz = jnp.where(ev_winner, state.size[v_idx].astype(I32), 0)
            freed_t = jnp.zeros((Tn,), I32).at[v_ten].add(v_sz)   # [T]
            allow_t = bud_t - occ_t + freed_t                     # [T]
            # Net charge sequence: insert sizes + SET byte deltas (growing
            # positive, shrinking negative — shrinks are never refused and
            # free room for later charges in the same step).
            charge_seq = jnp.where(ins_ok, obj_size.astype(I32), 0) + set_growth
            chargeable = ins_ok | growing_set
            # Round-ordered per-tenant running charge as ONE [B, T] one-hot
            # cumsum (integer, so exactly the old per-tenant masked cumsum
            # loop without the O(Tn) traced passes over B).
            onehot = (tb_i[:, None] == jnp.arange(Tn, dtype=I32)[None, :])
            cum = jnp.cumsum(jnp.where(onehot, charge_seq[:, None], 0),
                             axis=0)                              # [B, T]
            cum_own = jnp.take_along_axis(cum, tb_i[:, None], axis=1)[:, 0]
            cancel = chargeable & (cum_own > allow_t[tb_i])
            plain = plain & ~cancel
            fallback_hist = fallback_hist & ~cancel
            fallback_obj = fallback_obj & ~cancel
            ins_ok = ins_ok & ~cancel
            dropped = dropped | cancel
            set_ok = hit & is_write & ~(growing_set & cancel)
        else:
            set_ok = hit & is_write
        # Apply SET payload/size writes (deferred from step 2 past the gate).
        val_idx = jnp.where(set_ok, slot, n_slots_total)
        vals = state.values.at[val_idx].set(values, mode="drop")
        sizes_upd = state.size.at[val_idx].set(obj_size, mode="drop")

        # Expert bitmap per victim: experts whose candidate matches, plus the
        # evicting op's chosen expert (Fig. 9).
        cand_rep = jnp.repeat(cand_slot, V, axis=0)               # [B*V, E]
        e_rep = jnp.repeat(e_choice, V)                           # [B*V]
        bmap = jnp.sum(((cand_rep == victims[:, None]).astype(U32)
                        << jnp.arange(E, dtype=U32)[None, :]), axis=1)
        bmap = bmap | (U32(1) << e_rep.astype(U32))

        # GreedyDual inflation: L <- max(L, evicted victim's H) for GDS-family.
        gds_L = state.gds_L
        gds_ids = [i for i, n in enumerate(names) if prio.REGISTRY[n].gds_family]
        if gds_ids:
            v_md = _md_view(state, jnp.maximum(victims, 0), jnp.repeat(ts_req, V))
            v_prio = prio.priorities(v_md, names)                 # [B*V, E]
            vp = jnp.stack([v_prio[:, i] for i in gds_ids], axis=1)
            vp = jnp.where(ev_winner[:, None], vp, -jnp.inf)
            gds_L = jnp.maximum(gds_L, jnp.max(vp, initial=-jnp.inf))

        # History insertion (FAA on the global counter + slot tag + bmap write).
        write_hist = ev_winner & adaptive & cfg.use_lwh
        hist_rank = jnp.cumsum(write_hist.astype(I32)) - 1
        hist_ids = (state.hist_ctr + hist_rank.astype(U32))
        # i32 here: the FAA tally at step 7 consumes it as i32, so converting
        # to U32 eagerly would force an i32->u32->i32 round-trip (JX002); the
        # one u32 consumer (hist_ctr) converts at its use site instead.
        n_hist = jnp.sum(write_hist)

    with jax.named_scope("ditto.apply"):
        # ------------------------------------------------------------------
        # 6. Apply: inserts, then evictions (so a victim that collides with a
        #    bucket-fallback overwrite target nets out exactly in n_cached).
        # ------------------------------------------------------------------
        ii = jnp.where(ins_ok, ins_slot, n_slots_total)
        key2 = state.key.at[ii].set(keys_b, mode="drop")
        khash2 = state.key_hash.at[ii].set(kh, mode="drop")
        sizes3 = sizes_upd.at[ii].set(obj_size, mode="drop")
        ptr3 = state.ptr.at[ii].set(U32(0), mode="drop")
        ins_ts3 = state.insert_ts.at[ii].set(ts_req, mode="drop")
        last_ts = last_ts.at[ii].set(ts_req, mode="drop")
        freq = freq.at[ii].set(U32(1), mode="drop")
        ext = ext.at[ii].set(prio.fresh_ext(ts_req, (B,)), mode="drop")
        vals = vals.at[ii].set(values, mode="drop")

        ev_idx = jnp.where(ev_winner, victims, n_slots_total)
        sizes3 = sizes3.at[ev_idx].set(
            jnp.where(write_hist, U32(SIZE_HISTORY), U32(SIZE_EMPTY)), mode="drop")
        ptr3 = ptr3.at[ev_idx].set(
            jnp.where(write_hist, hist_ids, U32(0)), mode="drop")
        ins_ts3 = ins_ts3.at[ev_idx].set(bmap, mode="drop")

        n_cached = (state.n_cached + jnp.sum(plain).astype(I32)
                    + jnp.sum(fallback_hist).astype(I32) - n_evict)
        # Byte occupancy is recomputed exactly from the final table (one
        # reduce over a column the step already rewrote): inserts charge
        # obj_size, evictions credit the victim's size, SET re-sizes and
        # bucket-fallback overwrites net out — the invariant
        # `bytes_cached == sum(live sizes)` holds by construction and can
        # never drift the way an incremental counter could.
        bytes_cached = jnp.sum(
            jnp.where(_is_live(sizes3), sizes3, U32(0))).astype(I32)
        # Per-tenant occupancy: same recompute-exactly discipline as
        # bytes_cached (one scatter-add over the tenant column), so the
        # partitioning invariant `tenant_bytes[t] == sum(live sizes of t)`
        # can never drift either.  Single-tenant: the column stays untouched
        # and the occupancy is definitionally the global one.
        if multi:
            tenant2 = state.tenant.at[ii].set(tenant_b, mode="drop")
            tenant_bytes = jnp.zeros((Tn,), I32).at[tenant2.astype(I32)].add(
                jnp.where(_is_live(sizes3), sizes3, U32(0)).astype(I32))
        else:
            tenant2 = state.tenant
            tenant_bytes = bytes_cached[None]

        result_vals = state.values[jnp.maximum(slot, 0)]

        # ------------------------------------------------------------------
        # 6b. L0 coherence tokens + fill (DESIGN.md §15).  Every bucket that
        #     commits a mutation this step — SET payload, insert, eviction —
        #     bumps its version exactly once; the bump is what invalidates
        #     other lanes' L0 copies.  Fills are restricted to non-write GET
        #     hits on buckets with ZERO bumps this step: for those the
        #     step-entry snapshot the hit served IS the post-step table
        #     content, so entry validity (token match) always implies value
        #     currency.  One fill per lane per step (the last fillable
        #     request, matching last-writer-wins recency); victim order is
        #     same-key refresh → first empty slot → local LRU.
        # ------------------------------------------------------------------
        if l0:
            nb = cfg.n_buckets
            touched = jnp.zeros((nb + 1,), bool)
            touched = touched.at[jnp.where(set_ok | ins_ok, bucket, nb)].set(True)
            touched = touched.at[jnp.where(ev_winner, victims // A, nb)].set(True)
            touched = touched[:nb]                                 # bool[nb]
            bucket_ver2 = state.bucket_ver + touched.astype(U32)

            fill_ok = hit & ~is_write & ~shadow_b & ~touched[bucket]   # [B]
            pos = jnp.arange(B, dtype=I32)
            last_fill = jnp.full((C,), -1, I32).at[
                jnp.where(fill_ok, lane_b, C)].max(pos, mode="drop")   # [C]
            f_req = jnp.maximum(last_fill, 0)                      # [C] -> B idx
            do_fill = last_fill >= 0
            fill_key = keys_b[f_req]
            fill_bkt = bucket[f_req]
            fill_tok = state.bucket_ver[fill_bkt]   # step-entry == post-step
            fill_sz = old_sz[f_req]
            fill_val = result_vals[f_req]
            fill_ts = ts_req[f_req]

            # Drop stale entries, then refresh the local LRU stamp of every
            # entry that served an L0 hit this step (max request-ts wins).
            key1 = jnp.where(l0_stale, U32(0), clients.l0_key)
            last1 = clients.l0_last.at[
                jnp.where(l0_hit, lane_b, C), l0_idx].max(ts_req, mode="drop")
            same = key1 == fill_key[:, None]                       # [C, L0]
            empty = key1 == 0
            pick = jnp.where(
                jnp.any(same, axis=1), jnp.argmax(same, axis=1),
                jnp.where(jnp.any(empty, axis=1), jnp.argmax(empty, axis=1),
                          jnp.argmin(last1, axis=1)))              # [C]
            wl = jnp.where(do_fill, jnp.arange(C, dtype=I32), C)
            l0_key2 = key1.at[wl, pick].set(fill_key, mode="drop")
            l0_bkt2 = clients.l0_bkt.at[wl, pick].set(
                fill_bkt.astype(I32), mode="drop")
            l0_tok2 = clients.l0_tok.at[wl, pick].set(fill_tok, mode="drop")
            l0_sz2 = clients.l0_sz.at[wl, pick].set(fill_sz, mode="drop")
            l0_val2 = clients.l0_val.at[wl, pick].set(fill_val, mode="drop")
            l0_last2 = last1.at[wl, pick].set(fill_ts, mode="drop")
            l0_seen2 = jnp.broadcast_to(state.l0_epoch, (C,))
        else:
            bucket_ver2 = state.bucket_ver

        new_state = CacheState(
            key=key2, key_hash=khash2, size=sizes3, ptr=ptr3,
            insert_ts=ins_ts3, last_ts=last_ts, freq=freq, ext=ext, values=vals,
            n_cached=n_cached, bytes_cached=bytes_cached,
            hist_ctr=state.hist_ctr + n_hist.astype(U32),
            clock=clock + U32(G), weights=gw if multi else gw[0], gds_L=gds_L,
            capacity_blocks=state.capacity_blocks,
            tenant=tenant2, tenant_bytes=tenant_bytes,
            tenant_budget=state.tenant_budget,
            bucket_ver=bucket_ver2, l0_epoch=state.l0_epoch)
        cl_upd = dict(
            local_weights=local_w if multi else local_w[:, 0],
            penalty_acc=pacc if multi else pacc[:, 0],
            penalty_cnt=pcnt if multi else pcnt[:, 0])
        if l0:
            cl_upd.update(l0_key=l0_key2, l0_bkt=l0_bkt2, l0_tok=l0_tok2,
                          l0_sz=l0_sz2, l0_val=l0_val2, l0_last=l0_last2,
                          l0_seen_epoch=l0_seen2)
        new_clients = clients._replace(**cl_upd)

    with jax.named_scope("ditto.account"):
        # ------------------------------------------------------------------
        # 7. Remote-op accounting (cost model; see DESIGN.md §2).
        # ------------------------------------------------------------------
        n_op = jnp.sum(op)
        n_hit = jnp.sum(hit)
        n_set = jnp.sum(op & is_write)
        n_ins = jnp.sum(ins_ok)
        sf = cfg.use_sfht
        reads = (n_op                         # bucket probe (metadata inline iff SFHT)
                 + (0 if sf else n_hit)       # separate metadata fetch
                 + n_hit                      # object payload read
                 # without the embedded history, every miss probes a separate
                 # history hash index (an extra RTT on the regret path)
                 + (0 if (cfg.use_lwh or not adaptive) else jnp.sum(miss))
                 + jnp.sum(evicting) * (1 if sf else K))  # sampling read(s)
        # Without the lightweight history, evictions maintain a separate FIFO
        # queue + hash index (entry write, index insert, queue-tail FAA).
        sep_hist = 0 if (cfg.use_lwh or not adaptive) else n_evict
        writes = (n_hit * (1 if sf else 2)    # stateless metadata update(s)
                  + n_ins * 2                 # object write + slot metadata init
                  + jnp.sum(write_hist)       # embedded expert-bitmap write
                  + sep_hist * 2)
        cas = n_ins + jnp.sum(ev_winner)      # slot atomic installs/tags
        faa = n_faa + n_hist + sep_hist
        # Wire-byte accounting (payload-size-dependent reads/writes, DESIGN.md
        # §10): slot structures move at 32B apiece (16B atomic field + 16B
        # inline metadata), object payloads at their real size*64B — this is
        # what makes the cost model's bandwidth bound respond to sized traces.
        SLOT_B = 32
        hit_blocks = jnp.sum(jnp.where(hit, old_sz, U32(0))).astype(I32)
        miss_blocks = jnp.sum(jnp.where(miss, obj_size, U32(0))).astype(I32)
        ins_blocks = jnp.sum(jnp.where(ins_ok, obj_size, U32(0))).astype(I32)
        set_blocks = jnp.sum(jnp.where(hit & is_write, obj_size,
                                       U32(0))).astype(I32)
        read_b = (n_op * A * SLOT_B           # bucket probe
                  + (0 if sf else n_hit * SLOT_B)
                  + hit_blocks * 64           # object payload reads
                  + (0 if (cfg.use_lwh or not adaptive)
                     else jnp.sum(miss) * SLOT_B)
                  + jnp.sum(evicting) * (W if sf else K) * SLOT_B)
        write_b = (n_hit * (SLOT_B // 2 if sf else SLOT_B)
                   + ins_blocks * 64 + n_ins * SLOT_B   # payload + slot init
                   + set_blocks * 64                    # SET payload rewrite
                   + jnp.sum(write_hist) * 16 + sep_hist * SLOT_B)
        if shadow is None:
            gets_v, sets_v = n_op - n_set, n_set
            hits_v, misses_v = n_hit, jnp.sum(miss)
            hit_bytes_v, miss_bytes_v = hit_blocks * 64, miss_blocks * 64
            n_rep = 0
        else:
            # Mirror ops execute (RDMA/wire counters above see them) but are
            # invisible to the client-facing ratios — they are replication
            # traffic, not offered load.
            sh = shadow.reshape(B) & op
            vis = op & ~sh
            n_set_v = jnp.sum(vis & is_write)
            gets_v, sets_v = jnp.sum(vis) - n_set_v, n_set_v
            hits_v = jnp.sum(hit & ~sh)
            misses_v = jnp.sum(miss & ~sh)
            hit_bytes_v = jnp.sum(
                jnp.where(hit & ~sh, old_sz, U32(0))).astype(I32) * 64
            miss_bytes_v = jnp.sum(
                jnp.where(miss & ~sh, obj_size, U32(0))).astype(I32) * 64
            n_rep = jnp.sum(sh)
        if l0:
            # L0 hits are client-visible (gets/hits/hit_bytes keep their
            # offered-load meaning) but issue ZERO rdma ops/bytes — that
            # delta against the remote counters above is the wire-byte
            # offload the tier exists to buy.
            gets_v = gets_v + n_l0_hit
            hits_v = hits_v + n_l0_hit
            hit_bytes_v = hit_bytes_v + jnp.sum(
                jnp.where(l0_hit, l0_size, U32(0))).astype(I32) * 64
        stats = stats_add(
            stats, rdma_read=reads, rdma_write=writes, rdma_cas=cas,
            rdma_faa=faa, rpc=n_sync, gets=gets_v, sets=sets_v,
            rdma_read_bytes=read_b, rdma_write_bytes=write_b,
            hit_bytes=hit_bytes_v, miss_bytes=miss_bytes_v,
            hits=hits_v, misses=misses_v, regrets=jnp.sum(regret),
            evictions=n_evict, bucket_evictions=jnp.sum(fallback_obj),
            insert_drops=jnp.sum(dropped), fc_hits=n_fc_hit,
            fc_flushes=n_faa, weight_syncs=n_sync, replica_writes=n_rep)
        if l0:
            stats = stats_add(stats, l0_hits=n_l0_hit,
                              l0_invalidations=n_l0_inval)
            # Merge the locally-served requests back into the caller-facing
            # result (they were masked to padding for the remote path).
            hit = hit | l0_hit
            result_vals = jnp.where(l0_hit[:, None], l0_value, result_vals)

    if cfg.sanitize:
        # dittolint pass 3 (DESIGN.md §12): jittable invariant checks on
        # the state this step produced.  Static gate — sanitize=False
        # traces to exactly the same jaxpr as before the hook existed.
        from repro.analysis import sanitize as _sanitize
        _sanitize.check_state(cfg, new_state)
        _sanitize.check_clients(cfg, new_clients)
        _sanitize.check_step(cfg, state, new_state)

    return new_state, new_clients, stats, AccessResult(
        hit=hit.reshape(G, C), value=result_vals.reshape(G, C, -1),
        evicted=evicting.reshape(G, C), regret=regret.reshape(G, C))


def access(cfg: CacheConfig, state: CacheState, clients: ClientState,
           stats: OpStats, keys: jnp.ndarray, *,
           is_write: jnp.ndarray | None = None,
           obj_size: jnp.ndarray | None = None,
           values: jnp.ndarray | None = None,
           tenant: jnp.ndarray | None = None,
           insert_on_miss: bool = True,
           ):
    """One single-round cache step: GET each key; read-through insert on
    miss.  Thin G=1 wrapper over :func:`access_group` (identical
    semantics to the paper's one-op-per-client concurrent step).

    Args:
      keys: u32[C]; 0 marks a padded no-op lane.
    """
    state, clients, stats, res = access_group(
        cfg, state, clients, stats, keys[None, :],
        is_write=None if is_write is None else is_write[None, :],
        obj_size=None if obj_size is None else obj_size[None, :],
        values=None if values is None else values[None],
        tenant=None if tenant is None else tenant[None, :],
        insert_on_miss=insert_on_miss)
    return state, clients, stats, AccessResult(
        hit=res.hit[0], value=res.value[0], evicted=res.evicted[0],
        regret=res.regret[0])


# ----------------------------------------------------------------------
# Trace drivers: lax.scan over [T, C] (one round per step) or
# [NG, G, C] planned-group request streams.
# ----------------------------------------------------------------------

class TraceResult(NamedTuple):
    state: CacheState
    clients: ClientState
    stats: OpStats
    hits: jnp.ndarray      # i32[T] per-round hit counts
    ops: jnp.ndarray       # i32[T] per-round op counts
    weights: jnp.ndarray   # f32[T, E] global weight trajectory
                           # (grouped runs: step-granular, repeated per round)


def _run_trace_impl(cfg: CacheConfig, state: CacheState,
                    clients: ClientState, keys: jnp.ndarray,
                    is_write: jnp.ndarray | None = None,
                    obj_size: jnp.ndarray | None = None,
                    tenant: jnp.ndarray | None = None,
                    stats: OpStats | None = None) -> TraceResult:
    """Run a [T, C] trace (T steps of C concurrent client ops).  Counters
    accumulate onto ``stats`` (fresh zeros when None)."""
    T, C = keys.shape
    if is_write is None:
        is_write = jnp.zeros((T, C), bool)
    if obj_size is None:
        obj_size = jnp.ones((T, C), U32)
    if tenant is None:
        tenant = jnp.zeros((T, C), U32)
    if stats is None:
        stats = init_stats()

    def step(carry, xs):
        st, cl, sa = carry
        k, w, sz, tn = xs
        st, cl, sa, res = access(cfg, st, cl, sa, k, is_write=w, obj_size=sz,
                                 tenant=tn)
        out = (jnp.sum(res.hit).astype(I32), jnp.sum(k != 0).astype(I32),
               st.weights)
        return (st, cl, sa), out

    (state, clients, stats), (hits, ops, weights) = jax.lax.scan(
        step, (state, clients, stats), (keys, is_write, obj_size, tenant))
    return TraceResult(state, clients, stats, hits, ops, weights)


def _run_trace_grouped_impl(cfg: CacheConfig, state: CacheState,
                            clients: ClientState, keys: jnp.ndarray,
                            is_write: jnp.ndarray | None = None,
                            obj_size: jnp.ndarray | None = None,
                            tenant: jnp.ndarray | None = None,
                            stats: OpStats | None = None) -> TraceResult:
    """Run a planned [NG, G, C] grouped trace: one scan step retires a
    whole G-round request group (see ``workloads.plan.plan_groups``).

    Returns per-round hit/op counts ([NG*G]) so grouped and sequential
    runs compare round-for-round; the weight trajectory is step-granular
    (each group's end weights repeated for its G rounds).  Counters
    accumulate onto ``stats`` (fresh zeros when None)."""
    NG, G, C = keys.shape
    if is_write is None:
        is_write = jnp.zeros((NG, G, C), bool)
    if obj_size is None:
        obj_size = jnp.ones((NG, G, C), U32)
    if tenant is None:
        tenant = jnp.zeros((NG, G, C), U32)
    if stats is None:
        stats = init_stats()

    def step(carry, xs):
        st, cl, sa = carry
        k, w, sz, tn = xs
        st, cl, sa, res = access_group(cfg, st, cl, sa, k,
                                       is_write=w, obj_size=sz, tenant=tn)
        out = (jnp.sum(res.hit, axis=1).astype(I32),
               jnp.sum(k != 0, axis=1).astype(I32), st.weights)
        return (st, cl, sa), out

    (state, clients, stats), (hits, ops, weights) = jax.lax.scan(
        step, (state, clients, stats), (keys, is_write, obj_size, tenant))
    return TraceResult(state, clients, stats, hits.reshape(-1),
                       ops.reshape(-1), jnp.repeat(weights, G, axis=0))


def _deprecated_entrypoint(name: str) -> None:
    import warnings
    warnings.warn(
        f"{name} is deprecated; drive traces through repro.core.execute() "
        "(DESIGN.md §13) — it wraps the same engine behind one planned, "
        "width-adaptive surface", DeprecationWarning, stacklevel=3)


def run_trace(cfg: CacheConfig, state: CacheState, clients: ClientState,
              keys: jnp.ndarray, is_write: jnp.ndarray | None = None,
              obj_size: jnp.ndarray | None = None,
              tenant: jnp.ndarray | None = None) -> TraceResult:
    """Deprecated sequential trace driver: use ``repro.core.execute``
    with ``plan=None`` (bit-identical results)."""
    _deprecated_entrypoint("run_trace")
    return _run_trace_impl(cfg, state, clients, keys, is_write, obj_size,
                           tenant)


def run_trace_grouped(cfg: CacheConfig, state: CacheState,
                      clients: ClientState, keys: jnp.ndarray,
                      is_write: jnp.ndarray | None = None,
                      obj_size: jnp.ndarray | None = None,
                      tenant: jnp.ndarray | None = None,
                      stats: OpStats | None = None) -> TraceResult:
    """Deprecated grouped trace driver: use ``repro.core.execute`` with
    a precomputed plan or ``plan="adaptive"`` (bit-identical results for
    the same plan)."""
    _deprecated_entrypoint("run_trace_grouped")
    return _run_trace_grouped_impl(cfg, state, clients, keys, is_write,
                                   obj_size, tenant, stats)


def make_cache(cfg: CacheConfig, n_clients: int, seed: int = 0):
    return init_cache(cfg), init_clients(cfg, n_clients, seed), init_stats()
