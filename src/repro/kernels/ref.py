"""Pure-jnp oracles for the Pallas kernels.

These are the semantics contracts: every kernel in this package must
``allclose`` against these on randomized shape/dtype sweeps (run in
interpret mode on CPU, compiled on TPU).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.hashing import bucket_of, hash_key

def priorities_ref(size, insert_ts, last_ts, freq, clock, experts):
    """Stacked eviction priorities [..., E] for the kernel's expert set.

    Experts here are the kernel-supported subset: lru/lfu/fifo/size/
    hyperbolic — pure arithmetic over the four default metadata columns."""
    out = []
    for e in experts:
        if e == "lru":
            out.append(last_ts)
        elif e == "lfu":
            out.append(freq)
        elif e == "fifo":
            out.append(insert_ts)
        elif e == "size":
            out.append(-size)
        elif e == "hyperbolic":
            out.append(freq / jnp.maximum(clock - insert_ts, 1.0))
        else:
            raise ValueError(e)
    return jnp.stack(out, axis=-1)


def ranked_eviction_ref(size, insert_ts, last_ts, freq, offsets, e_choice,
                        must_evict, quota, ts, *, window: int, k: int,
                        experts, tenant=None, tfilt=None):
    """Reference for the quota-extended ranked eviction kernel.

    Mirrors `core/cache.py` step 5: priorities over the sampled window
    (evaluated at each op's own timestamp ``ts`` [B]), chosen-expert
    stable ranking, and the byte-deficit take rule — an evicting op
    claims the shortest ranked prefix of sampled victims whose summed
    sizes (64B blocks) reach its ``quota`` (scalar or per-op i32[B]),
    at most ``k`` victims.  Uniform 1-block objects recover the old
    take-`quota`-victims rule.  Table arrays are the [C] slot columns;
    windows wrap mod C.

    Multi-tenant scoping (DESIGN.md §11): ``tenant`` is the per-slot
    owner column and ``tfilt`` i32[B] restricts op b's sample
    to slots of that tenant (-1 = unfiltered shared-pool sample); both
    default to the single-tenant behavior.

    Returns:
      victims: i32[B, k] ranked victim slots, -1 where not taken.
      cand:    i32[B, E] per-expert argmin candidate.
    """
    B = offsets.shape[0]
    C = size.shape[0]
    quota = jnp.broadcast_to(jnp.asarray(quota, jnp.float32), (B,))
    idx = (offsets[:, None] + jnp.arange(window)[None, :]) % C    # [B, W]
    f32 = lambda x: x[idx].astype(jnp.float32)
    s = f32(size)
    live = (s > 0) & (s < 255)
    if tenant is not None and tfilt is not None:
        tf = jnp.asarray(tfilt, jnp.int32)
        live = live & ((tf[:, None] < 0)
                       | (tenant[idx].astype(jnp.int32) == tf[:, None]))
    in_sample = live & (jnp.cumsum(live, axis=1) <= k)
    pr = priorities_ref(s, f32(insert_ts), f32(last_ts), f32(freq),
                        ts[:, None], experts)                     # [B, W, E]
    pr = jnp.where(in_sample[..., None], pr, jnp.inf)
    cand_w = jnp.argmin(pr, axis=1)                               # [B, E]
    cand = jnp.take_along_axis(idx, cand_w, axis=1)

    pr_sel = jnp.take_along_axis(
        pr, e_choice[:, None, None], axis=2)[:, :, 0]             # [B, W]
    # The oracle ranks the whole window by full sort for clarity; the
    # fused kernel argmin-peels.  dittolint: disable=DL003
    order = jnp.argsort(pr_sel, axis=1)                           # stable
    ranked_idx = jnp.take_along_axis(idx, order, axis=1)
    ranked_live = jnp.take_along_axis(in_sample, order, axis=1)
    ranked_blocks = jnp.where(ranked_live,
                              jnp.take_along_axis(s, order, axis=1), 0.0)
    # Exclusive prefix sum of freed blocks: take a victim while the
    # blocks freed *before* it still fall short of the quota.
    freed_before = jnp.cumsum(ranked_blocks, axis=1) - ranked_blocks
    take = ((freed_before < quota[:, None]) & ranked_live
            & must_evict[:, None])
    victims = jnp.where(take, ranked_idx, -1)[:, :k]
    return victims.astype(jnp.int32), cand.astype(jnp.int32)


def access_probe_ref(table_key, table_size, table_hash, table_ptr, keys,
                     hist_ctr, *, assoc: int, history_len: int):
    """Reference fused Get-path probe: bucket match + history match.

    Returns (found bool[B], slot i32[B] (-1 miss), hist_found bool[B],
    hist_slot i32[B])."""
    n_buckets = table_key.shape[0] // assoc
    kh = hash_key(keys)
    bucket = bucket_of(kh, n_buckets)
    slots = bucket[:, None] * assoc + jnp.arange(assoc)[None, :]
    sz = table_size[slots]
    live = (sz > 0) & (sz < 255)
    match = live & (table_key[slots] == keys[:, None])
    found = jnp.any(match, axis=1)
    slot = jnp.take_along_axis(slots, jnp.argmax(match, axis=1)[:, None],
                               axis=1)[:, 0]
    is_hist = sz == 255
    age = (jnp.asarray(hist_ctr, jnp.uint32)
           - table_ptr[slots].astype(jnp.uint32)).astype(jnp.uint32)
    h_valid = is_hist & (age < jnp.uint32(history_len))
    h_match = h_valid & (table_hash[slots] == kh[:, None])
    hist_found = jnp.any(h_match, axis=1) & ~found
    hslot = jnp.take_along_axis(slots, jnp.argmax(h_match, axis=1)[:, None],
                                axis=1)[:, 0]
    return (found, jnp.where(found, slot, -1).astype(jnp.int32),
            hist_found, hslot.astype(jnp.int32))


def hit_metadata_update_ref(freq, last_ts, ext, hit_slots, hit_ts,
                            emit_slots, emit_deltas, *, lruk_k=None,
                            lrfu_lambda=None):
    """Reference fused hit-side metadata update.

    last_ts[s] = max(last_ts[s], ts_eff) and the extension-column update
    at hit slots, where ts_eff is the max per-hit timestamp among the
    batch's hits on s; freq[s] += delta at FC-flush slots (combining
    FAA). hit_slots/emit_slots use -1 as no-op; hit_ts[Bh] carries each
    hit's request timestamp."""
    from repro.core.priority import LRFU_LAMBDA, LRUK_K
    lruk_k = float(LRUK_K) if lruk_k is None else lruk_k
    lrfu_lambda = LRFU_LAMBDA if lrfu_lambda is None else lrfu_lambda
    n = freq.shape[0]
    ok_h = hit_slots >= 0
    hidx = jnp.where(ok_h, hit_slots, n)
    ok_e = emit_slots >= 0
    eidx = jnp.where(ok_e, emit_slots, n)
    freq2 = freq.at[eidx].add(jnp.where(ok_e, emit_deltas, 0.0), mode="drop")
    ts_eff = jnp.zeros((n + 1,), last_ts.dtype).at[hidx].max(
        hit_ts.astype(last_ts.dtype))[:n]
    touched = jnp.zeros((n + 1,), bool).at[hidx].set(True)[:n]
    last2 = jnp.where(touched, jnp.maximum(last_ts, ts_eff), last_ts)
    clock_col = ts_eff.astype(jnp.float32)
    new_freq = freq + 1.0
    widx = jnp.mod(new_freq, lruk_k)
    ts0 = jnp.where(widx == 0.0, clock_col, ext[:, 0])
    ts1 = jnp.where(widx == 1.0, clock_col, ext[:, 1])
    gap = clock_col - last_ts
    crf = 1.0 + ext[:, 2] * jnp.exp2(-lrfu_lambda * gap)
    new_ext = jnp.stack([ts0, ts1, crf, gap], axis=-1)
    ext2 = jnp.where(touched[:, None], new_ext, ext)
    return freq2, last2, ext2
