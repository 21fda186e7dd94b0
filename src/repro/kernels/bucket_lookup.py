"""Fused Get-path probe Pallas kernel (``access_probe``).

The client-side Get path of the ``fused`` backend of ``core/cache.py``:
one pass that performs the bucket probe *and* the embedded-history match
(paper §4.3.1) against the sample-friendly table, returning
(found, slot, hist_found, hist_slot).  On DM this is the 1-RDMA_READ
bucket fetch; here the bucket rows stream from the VMEM-resident slot
columns.

Layout (``runtime.as_rows``): each column is ``i32[n / 128, 128]``, so a
bucket of ``assoc`` slots is the lane range ``[b*assoc % 128, +assoc)``
of row ``b*assoc // 128``.  Per request block, a scalar loop copies each
request's bucket row (row index from SMEM) into a VMEM scratch; the
match then runs vectorized over ``[block_b, 128]`` with a lane mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import bucket_of, hash_key
from repro.kernels.runtime import (LANES, VMEM_LIMIT_BYTES, as_column,
                                   as_i32, as_rows, auto_block_b,
                                   resolve_interpret)


def _first_lane(mask, lane):
    """[b, 1] index of the first True lane of each row (LANES if none)."""
    return jnp.min(jnp.where(mask, lane, LANES), axis=1, keepdims=True)


def _probe_kernel(rows_ref, hctr_ref, tk_ref, ts_ref, th_ref, tp_ref,
                  keys_ref, kh_ref, base_ref, found_ref, slot_ref, hfound_ref,
                  hslot_ref, gk, gs, gh, gp, *, assoc, history_len, block_b):
    # Gather each request's bucket row: scalar row index, one row copy
    # per column (the bucket read).
    first_req = pl.program_id(0) * block_b

    def gather(i, carry):
        r = rows_ref[first_req + i]
        for src, dst in ((tk_ref, gk), (ts_ref, gs), (th_ref, gh),
                         (tp_ref, gp)):
            dst[pl.ds(i, 1), :] = src[pl.ds(r, 1), :]
        return carry

    jax.lax.fori_loop(0, block_b, gather, 0)

    base = base_ref[...]                                   # [b, 1] slot
    lo = base & (LANES - 1)                                # lane offset
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_b, LANES), 1)
    in_bucket = (lane >= lo) & (lane < lo + assoc)
    size = gs[...]

    # Live-object match (first matching slot, as the reference argmax).
    live = in_bucket & (size != 0) & (size != 255)
    first = _first_lane(live & (gk[...] == keys_ref[...]), lane)
    found = first < LANES

    # Embedded history match on the same bucket read: size == 255 slots
    # tagged with a logical-FIFO id in `ptr`.  The unsigned age
    # (hist_ctr - ptr) < history_len is the signed age in [0, len).
    age = hctr_ref[0] - gp[...]
    h_valid = in_bucket & (size == 255) & (age >= 0) & (age < history_len)
    hfirst = _first_lane(h_valid & (gh[...] == kh_ref[...]), lane)
    hit_hist = hfirst < LANES

    found_ref[...] = found.astype(jnp.int32)
    slot_ref[...] = jnp.where(found, base + first - lo, -1)
    hfound_ref[...] = (hit_hist & ~found).astype(jnp.int32)
    hslot_ref[...] = base + jnp.where(hit_hist, hfirst - lo, 0)


@functools.partial(jax.jit, static_argnames=("assoc", "history_len",
                                             "block_b", "interpret"))
def access_probe(table_key, table_size, table_hash, table_ptr, keys,
                 hist_ctr, *, assoc: int = 8, history_len: int = 1024,
                 block_b: int | None = None, interpret: bool | None = None):
    """Fused Get-path probe: bucket match + embedded-history match.

    table_*: u32[n_slots] (n_slots a multiple of 128); keys: u32[B];
    hist_ctr: u32[] global history counter.  Returns (found bool[B],
    slot i32[B] (-1 miss), hist_found bool[B], hist_slot i32[B] — the
    matching history slot, bucket base where there is no match,
    mirroring the reference path).  The batch is padded internally to a
    multiple of ``block_b``.
    """
    interpret = resolve_interpret(interpret)
    if LANES % assoc:
        raise ValueError(f"assoc={assoc} must divide {LANES}")
    if not 0 < history_len < 2**31:
        raise ValueError(f"history_len={history_len} out of range")
    B = keys.shape[0]
    block_b = block_b or auto_block_b(B, interpret)
    Bp = -(-B // block_b) * block_b
    n_buckets = table_key.shape[0] // assoc
    kh = hash_key(keys)
    base = bucket_of(kh, n_buckets).astype(jnp.int32) * assoc
    rows = jnp.concatenate([base // LANES,
                            jnp.zeros((Bp - B,), jnp.int32)])
    col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    fn = functools.partial(_probe_kernel, assoc=assoc,
                           history_len=history_len, block_b=block_b)
    out = pl.pallas_call(
        fn,
        grid=(Bp // block_b,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  vmem, vmem, vmem, vmem, col, col, col],
        out_specs=(col, col, col, col),
        out_shape=tuple(jax.ShapeDtypeStruct((Bp, 1), jnp.int32)
                        for _ in range(4)),
        scratch_shapes=[pltpu.VMEM((block_b, LANES), jnp.int32)
                        for _ in range(4)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(rows, as_i32(hist_ctr).reshape(1),
      as_rows(table_key), as_rows(table_size), as_rows(table_hash),
      as_rows(table_ptr), as_column(as_i32(keys), Bp),
      as_column(as_i32(kh), Bp), as_column(base, Bp))
    found, slot, hfound, hslot = (o[:B, 0] for o in out)
    return found.astype(bool), slot, hfound.astype(bool), hslot
