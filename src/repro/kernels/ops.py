"""Public jitted wrappers for the Pallas kernels.

``interpret`` resolves inside each kernel via
``repro.kernels.runtime.resolve_interpret`` — interpreter on CPU (the
kernel body executes in Python per the brief), compiled Mosaic on TPU.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.bucket_lookup import access_probe
from repro.kernels.flash_attention import flash_attention
from repro.kernels.metadata_update import hit_metadata_update
from repro.kernels.runtime import FUSED_MAX_SLOTS
from repro.kernels.sampled_eviction import KERNEL_EXPERTS, ranked_eviction

__all__ = ["ranked_eviction_op", "access_probe_op", "hit_metadata_update_op",
           "flash_attention_op", "KERNEL_EXPERTS", "FUSED_MAX_SLOTS"]


def ranked_eviction_op(size, insert_ts, last_ts, freq, offsets, e_choice,
                       must_evict, quota, ts, *, tenant=None, tfilt=None,
                       window=20, k=5, experts=("lru", "lfu"), block_b=None):
    """Quota-extended fused eviction: chosen-expert ranking, victims
    peeled until their summed sizes cover the op's `quota` blocks (at
    most k victims; `quota` is i32[B] or a scalar broadcast), each op
    evaluating time-dependent priorities at its own per-request
    timestamp ``ts`` [B].  Table columns are the cache's u32[C] slot
    columns; windows wrap mod C.  ``tenant`` (owner column) + ``tfilt``
    (i32[B], -1 = no filter) scope a budget-enforcing op's sample to its
    own tenant's slots (DESIGN.md §11)."""
    return ranked_eviction(
        size, insert_ts, last_ts, freq, offsets.astype(jnp.int32),
        e_choice.astype(jnp.int32), must_evict.astype(jnp.bool_), quota,
        ts.astype(jnp.float32), tenant,
        None if tfilt is None else tfilt.astype(jnp.int32),
        window=window, k=k, experts=tuple(experts), block_b=block_b)


def access_probe_op(table_key, table_size, table_hash, table_ptr, keys,
                    hist_ctr, *, assoc=8, history_len=1024, block_b=None):
    """Fused Get-path probe: bucket match + embedded-history match."""
    return access_probe(table_key, table_size, table_hash, table_ptr,
                        jnp.asarray(keys, jnp.uint32), hist_ctr, assoc=assoc,
                        history_len=history_len, block_b=block_b)


def hit_metadata_update_op(freq, last_ts, ext, hit_slots, hit_ts, emit_slots,
                           emit_deltas, *, block_c=512):
    """Fused hit-side metadata update: last_ts max + ext columns at hit
    slots (at per-hit request timestamps ``hit_ts`` [Bh]), combining freq
    FAA at FC-flush slots.  freq/last_ts are u32 end to end — no f32
    round-trip of timestamps."""
    return hit_metadata_update(freq, last_ts, ext, hit_slots, hit_ts,
                               emit_slots, emit_deltas, block_c=block_c)


def flash_attention_op(q, k, v, *, blk_q=128, blk_k=128):
    """Causal flash attention (forward): see kernels/flash_attention.py."""
    return flash_attention(q, k, v, blk_q=blk_q, blk_k=blk_k)
