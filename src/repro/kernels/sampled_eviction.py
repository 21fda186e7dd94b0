"""Fused ranked-eviction Pallas TPU kernel — the paper's hot loop.

One kernel fuses the whole client-side eviction decision (paper §4.2):
window gather from the sample-friendly table → E expert priorities on the
VPU → per-expert argmin candidates → the chosen expert's ranked victims
under a per-op block quota.  On DM this is one RDMA_READ + CPU work; on
TPU it is one VMEM-resident pass with zero HBM round trips between the
stages — the reason Ditto's sampling design is TPU-native where
linked-list LRU is not.

Layout (``runtime.as_rows``): each metadata column is
``i32[C / 128, 128]`` (u32 bits) and stays whole in VMEM.  A window of
``W <= 128`` consecutive slots from offset ``o`` lies in rows
``o // 128`` and the next one (mod the table), so per request a scalar
loop copies those two rows into two ``[block_b, 128]`` scratches whose
lane concatenation holds the window as the lane range
``[o % 128, o % 128 + W)``.  Lane order
is window order, so "first K live" and first-index argmin ties match the
reference exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import (LANES, VMEM_LIMIT_BYTES, as_column,
                                   as_rows, auto_block_b, resolve_interpret,
                                   u32_to_f32)

# Kernel-supported experts: pure arithmetic over the default metadata.
KERNEL_EXPERTS = ("lru", "lfu", "fifo", "size", "hyperbolic")

_BIG = 1 << 30


def _priority(e, size, ins, last, freq, clock):
    if e == "lru":
        return last
    if e == "lfu":
        return freq
    if e == "fifo":
        return ins
    if e == "size":
        return -size
    if e == "hyperbolic":
        return freq / jnp.maximum(clock - ins, 1.0)
    raise ValueError(e)


def _ranked_kernel(r0_ref, r1_ref, *refs, n_cols, window, k, experts,
                   block_b, n_slots):
    tables = refs[:n_cols]
    (lo_ref, off_ref, choice_ref, must_ref, quota_ref, tfilt_ref,
     ts_ref) = refs[n_cols:n_cols + 7]
    victim_ref, cand_ref = refs[n_cols + 7:n_cols + 9]
    scratch = refs[n_cols + 9:]

    # Gather each request's two window rows per column (the one sampling
    # read): scalar row indices from SMEM, one row copy per half.
    first_req = pl.program_id(0) * block_b

    def gather(i, carry):
        r0, r1 = r0_ref[first_req + i], r1_ref[first_req + i]
        for t, src in enumerate(tables):
            scratch[2 * t][pl.ds(i, 1), :] = src[pl.ds(r0, 1), :]
            scratch[2 * t + 1][pl.ds(i, 1), :] = src[pl.ds(r1, 1), :]
        return carry

    jax.lax.fori_loop(0, block_b, gather, 0)
    win = [jnp.concatenate([scratch[2 * t][...], scratch[2 * t + 1][...]],
                           axis=1) for t in range(n_cols)]

    shape = (block_b, 2 * LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lo = lo_ref[...]                                       # [b, 1]
    in_win = (lane >= lo) & (lane < lo + window)
    slot = off_ref[...] + lane - lo                        # window slot
    slot = jnp.where(slot >= n_slots, slot - n_slots, slot)

    size_u = win[0]
    elig = in_win & (size_u != 0) & (size_u != 255)
    if n_cols == 5:
        # Tenant-scoped sampling (DESIGN.md §11): an op with tfilt >= 0
        # only samples its own tenant's live objects; -1 is the classic
        # shared-pool sample.
        tfilt = tfilt_ref[...]
        elig = elig & ((tfilt < 0) | (win[4] == tfilt))
    # The sample: the first k eligible slots of the window.
    in_sample = jnp.zeros(shape, bool)
    for _ in range(k):
        m = jnp.min(jnp.where(elig & ~in_sample, lane, _BIG), axis=1,
                    keepdims=True)
        in_sample = in_sample | (lane == m)

    size = u32_to_f32(size_u)
    ins, last, freq = (u32_to_f32(w) for w in win[1:4])
    clock = ts_ref[...]                                    # [b, 1] f32

    def first_min(pr):
        """(lane, value) of each row's first minimum over the window."""
        pr = jnp.where(in_win, pr, jnp.inf)
        val = jnp.min(pr, axis=1, keepdims=True)
        arg = jnp.min(jnp.where(in_win & (pr == val), lane, _BIG), axis=1,
                      keepdims=True)
        return arg, val

    def pick(x, arg):
        return jnp.sum(jnp.where(lane == arg, x, 0), axis=1, keepdims=True)

    # All-expert priorities (for the per-victim expert bitmap) and the
    # chosen expert's priority row, inf-masked outside the sample.
    choice = choice_ref[...]
    cand_col = jax.lax.broadcasted_iota(jnp.int32, (block_b, len(experts)),
                                        1)
    cand = jnp.zeros((block_b, len(experts)), jnp.int32)
    pr_sel = None
    for ei, e in enumerate(experts):
        pr = jnp.where(in_sample, _priority(e, size, ins, last, freq, clock),
                       jnp.inf)
        arg, _ = first_min(pr)
        cand = jnp.where(cand_col == ei, pick(slot, arg), cand)
        pr_sel = pr if pr_sel is None else jnp.where(choice == ei, pr, pr_sel)
    cand_ref[...] = cand

    # Chosen-expert ranking with per-op BLOCK quota: peel off the lowest
    # priority sample until the freed blocks (victim sizes) cover the
    # op's byte deficit, at most k victims (== the shortest prefix of a
    # stable sort whose sizes sum past the quota, which is what the
    # reference path computes).
    must = must_ref[...] != 0
    quota = quota_ref[...].astype(jnp.float32)
    s_blocks = jnp.where(in_sample, size, 0.0)
    vcol = jax.lax.broadcasted_iota(jnp.int32, (block_b, k), 1)
    victims = jnp.full((block_b, k), -1, jnp.int32)
    freed = jnp.zeros((block_b, 1), jnp.float32)
    for j in range(k):
        arg, val = first_min(pr_sel)
        ok = (freed < quota) & (val < jnp.inf) & must
        victims = jnp.where((vcol == j) & ok, pick(slot, arg), victims)
        freed = freed + jnp.where(ok, pick(s_blocks, arg), 0.0)
        pr_sel = jnp.where(lane == arg, jnp.inf, pr_sel)
    victim_ref[...] = victims


@functools.partial(jax.jit, static_argnames=("window", "k", "experts",
                                             "block_b", "interpret"))
def ranked_eviction(size, insert_ts, last_ts, freq, offsets, e_choice,
                    must_evict, quota, ts, tenant=None, tfilt=None, *,
                    window: int = 20, k: int = 5, experts=("lru", "lfu"),
                    block_b: int | None = None,
                    interpret: bool | None = None):
    """Quota-extended fused eviction decision (the production hot path).

    Samples the first ``k`` live slots of the window of ``window``
    consecutive slots (mod C) from each op's offset and returns the
    chosen expert's priority *ranking* over them: victims peel off
    lowest priority first until their summed sizes cover the op's
    ``quota`` blocks, at most ``k`` per op (the byte-deficit catch-up
    eviction of ``core/cache.py`` step 5).

    Args:
      size/insert_ts/last_ts/freq: u32[C] slot columns (C a multiple of
        128; integral values of any dtype are cast to u32).
      offsets: i32[B] window starts in [0, C).
      e_choice: i32[B] chosen expert per op.
      must_evict: bool[B] — ops that must claim victims this step.
      quota: per-op block budget to free — i32[B] or a scalar broadcast.
      ts: f32[B] per-op logical clock (the op's round timestamp).
      tenant: u32[C] per-slot owner column; None = single-tenant.
      tfilt: i32[B] tenant filter per op — a budget-scoped op samples
        only slots of that tenant; -1 (or None) = shared-pool sample.
    Returns:
      victims: i32[B, k] ranked victim slots, -1 where not taken.
      cand:    i32[B, E] per-expert argmin candidate (the first window
               slot where the sample has no live object, as in the
               reference path).
    """
    interpret = resolve_interpret(interpret)
    if not 0 < window <= LANES:
        raise ValueError(f"window={window} must be in [1, {LANES}]")
    B = offsets.shape[0]
    C = size.shape[0]
    block_b = block_b or auto_block_b(B, interpret)
    Bp = -(-B // block_b) * block_b
    offsets = offsets.astype(jnp.int32)
    r0 = offsets // LANES
    r1 = jnp.where(r0 + 1 < C // LANES, r0 + 1, 0)
    cols = [size, insert_ts, last_ts, freq]
    if tenant is not None:
        cols.append(tenant)
    if tfilt is None:
        tfilt = jnp.full((B,), -1, jnp.int32)
    quota = jnp.broadcast_to(jnp.asarray(quota, jnp.int32), (B,))
    pad = lambda x: jnp.concatenate([x, jnp.zeros((Bp - B,), jnp.int32)])
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    col = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    fn = functools.partial(_ranked_kernel, n_cols=len(cols), window=window,
                           k=k, experts=tuple(experts), block_b=block_b,
                           n_slots=C)
    victims, cand = pl.pallas_call(
        fn,
        grid=(Bp // block_b,),
        in_specs=[smem, smem] + [vmem] * len(cols) + [col] * 7,
        out_specs=(pl.BlockSpec((block_b, k), lambda i: (i, 0)),
                   pl.BlockSpec((block_b, len(experts)), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((Bp, k), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, len(experts)), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((block_b, LANES), jnp.int32)
                        for _ in range(2 * len(cols))],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(pad(r0), pad(r1), *(as_rows(c) for c in cols),
      as_column(offsets % LANES, Bp), as_column(offsets, Bp),
      as_column(e_choice.astype(jnp.int32), Bp),
      as_column(must_evict.astype(jnp.int32), Bp), as_column(quota, Bp),
      as_column(tfilt.astype(jnp.int32), Bp, fill=-1),
      as_column(ts.astype(jnp.float32), Bp))
    return victims[:B], cand[:B]
