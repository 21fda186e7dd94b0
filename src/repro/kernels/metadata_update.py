"""Fused hit-side metadata-update Pallas kernel (the remote FAA +
stateless write, §4.2.1/4.2.2).

Applies one step's hits and FC-cache flushes to the metadata table:
  last_ts[s] = max(last_ts[s], ts)  + extension columns  at hit slots
  freq[s]   += delta                                     at flush slots

Formulated as dense one-hot compares per table tile: the [hits, tile]
match matrix reduces to the per-slot touched mask, effective timestamp
and combined FAA delta — the TPU-idiomatic shape of a combining scatter
(duplicate slots in the batch combine for free).

Grid: (table tiles of ``block_c`` slots) x (request chunks of
``_CHUNK``); each tile accumulates its chunks in VMEM scratch and
writes its outputs after the last one.  All
integer work is on i32 bitcasts of the u32 columns: sums wrap exactly
as u32 sums, and the u32 max runs as a signed max on sign-flipped bits
(Mosaic has no unsigned reductions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import (as_column, as_i32, resolve_interpret,
                                   u32_to_f32)

_CHUNK = 128
_SIGN = -2**31          # i32 bit pattern of 0x80000000


def _ext_constants():
    # Imported at kernel-trace time, not module time: core.cache imports
    # kernels.ops, so a module-level import here would be circular.
    from repro.core.priority import LRFU_LAMBDA, LRUK_K
    return float(LRUK_K), float(LRFU_LAMBDA)


def _hit_kernel(hit_ref, hts_ref, htsf_ref, emit_ref, delta_ref, freq_ref,
                last_ref, ext_ref, freq_out_ref, last_out_ref, ext_out_ref,
                touched_acc, ts_acc, tsf_acc, add_acc, *, block_c):
    c = pl.program_id(1)
    tile = (1, block_c)

    @pl.when(c == 0)
    def _init():
        touched_acc[...] = jnp.zeros(tile, jnp.int32)
        ts_acc[...] = jnp.full(tile, _SIGN, jnp.int32)
        tsf_acc[...] = jnp.full(tile, -jnp.inf, jnp.float32)
        add_acc[...] = jnp.zeros(tile, jnp.int32)

    pos = (jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, block_c), 1)
           + pl.program_id(0) * block_c)
    # Hit slots: the effective time of a slot is the max request
    # timestamp among the batch's hits on it (all equal under the
    # planner's bucket-disjoint grouping; a deterministic combine
    # otherwise) — mirrored by the reference path in core/cache.py.
    m = hit_ref[...] == pos                                # [chunk, tile]
    touched_acc[...] = jnp.maximum(touched_acc[...], jnp.max(
        m.astype(jnp.int32), axis=0, keepdims=True))
    ts_acc[...] = jnp.maximum(ts_acc[...], jnp.max(
        jnp.where(m, hts_ref[...], _SIGN), axis=0, keepdims=True))
    tsf_acc[...] = jnp.maximum(tsf_acc[...], jnp.max(
        jnp.where(m, htsf_ref[...], -jnp.inf), axis=0, keepdims=True))
    # FC-cache flush slots: the combining remote FAA on `freq`.
    add_acc[...] += jnp.sum(jnp.where(emit_ref[...] == pos, delta_ref[...],
                                      0), axis=0, keepdims=True)

    @pl.when(c == pl.num_programs(1) - 1)
    def _finish():
        touched = touched_acc[...] > 0
        clock_f = tsf_acc[...]
        freq = freq_ref[...]
        last = last_ref[...]
        freq_out_ref[...] = freq + add_acc[...]
        last_out_ref[...] = jnp.where(
            touched, jnp.maximum(last ^ _SIGN, ts_acc[...]) ^ _SIGN, last)
        # Extension metadata recomputed tile-wide from the step-entry
        # snapshot (mirror of priority.update_ext), then selected at
        # touched slots.  new_freq is integral, so x - k*floor(x/k) is
        # exactly mod(x, k) for the power-of-two ring width k.
        lruk_k, lrfu_lambda = _ext_constants()
        new_freq = u32_to_f32(freq) + 1.0
        widx = new_freq - lruk_k * jnp.floor(new_freq / lruk_k)
        gap = clock_f - u32_to_f32(last)
        ext = [ext_ref[i:i + 1, :] for i in range(4)]
        new_ext = [jnp.where(widx == 0.0, clock_f, ext[0]),
                   jnp.where(widx == 1.0, clock_f, ext[1]),
                   1.0 + ext[2] * jnp.exp2(-lrfu_lambda * gap),
                   gap]
        for i in range(4):
            ext_out_ref[i:i + 1, :] = jnp.where(touched, new_ext[i], ext[i])


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def hit_metadata_update(freq, last_ts, ext, hit_slots, hit_ts, emit_slots,
                        emit_deltas, *, block_c: int = 512,
                        interpret: bool | None = None):
    """Fused hit-side metadata update (the production hot path).

    One pass over the metadata table applying, per table tile:
      * ``last_ts[s] = max(last_ts[s], ts)`` and the extension-column
        update (LRU-K ring / LRFU CRF / LIRS IRR) at every hit slot,
        where ``ts`` is the max per-request timestamp among the batch's
        hits on the slot (request groups evaluate each round at its own
        logical time);
      * ``freq[s] += delta`` for every FC-cache flush (the remote FAA).

    freq/last_ts: u32[C] (integral values of any dtype are cast to u32);
    ext: f32[C, 4]; hit_slots: i32[Bh] and emit_slots: i32[Be] with -1 =
    no-op; hit_ts: u32[Bh] per-hit timestamps; emit_deltas: u32[Be].
    Returns updated (freq u32, last_ts u32, ext f32).  C is padded
    internally to a multiple of ``block_c`` (a multiple of 128).
    """
    interpret = resolve_interpret(interpret)
    if block_c % 128:
        raise ValueError(f"block_c={block_c} must be a multiple of 128")
    c = freq.shape[0]
    cp = -(-c // block_c) * block_c
    def table(x):                                  # [C] -> i32[1, Cp]
        return jnp.pad(as_i32(x), (0, cp - c)).reshape(1, cp)

    n = -(-max(hit_slots.shape[0], emit_slots.shape[0], 1)
          // _CHUNK) * _CHUNK
    req = pl.BlockSpec((_CHUNK, 1), lambda i, c: (c, 0))
    row = pl.BlockSpec((1, block_c), lambda i, c: (0, i))
    ext_tile = pl.BlockSpec((4, block_c), lambda i, c: (0, i))
    freq2, last2, ext2 = pl.pallas_call(
        functools.partial(_hit_kernel, block_c=block_c),
        grid=(cp // block_c, n // _CHUNK),
        in_specs=[req] * 5 + [row, row, ext_tile],
        out_specs=(row, row, ext_tile),
        out_shape=(jax.ShapeDtypeStruct((1, cp), jnp.int32),
                   jax.ShapeDtypeStruct((1, cp), jnp.int32),
                   jax.ShapeDtypeStruct((4, cp), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((1, block_c), dt) for dt in
                        (jnp.int32, jnp.int32, jnp.float32, jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(as_column(jnp.asarray(hit_slots, jnp.int32), n, -1),
      as_column(as_i32(hit_ts) ^ _SIGN, n, _SIGN),
      as_column(jnp.asarray(hit_ts).astype(jnp.uint32).astype(jnp.float32),
                n, -jnp.inf),
      as_column(jnp.asarray(emit_slots, jnp.int32), n, -1),
      as_column(as_i32(emit_deltas), n), table(freq), table(last_ts),
      jnp.pad(jnp.asarray(ext, jnp.float32).T, ((0, 0), (0, cp - c))))
    u32 = lambda x: jax.lax.bitcast_convert_type(x[0, :c], jnp.uint32)
    return u32(freq2), u32(last2), ext2[:, :c].T
