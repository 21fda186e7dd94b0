"""The records the pool holds, and how a run reads them back.

``execute()`` carries no payload: a SET or an insert through it writes a
record of zeros.  So set-up gives every slot of the filled pool the record
of the key it holds, ``record_bytes`` of words made from that key and the
seed, and after the window every slot's record is read back on the device
and classed: 0 all zeros (written through ``execute()`` since set-up), 1
the record of the slot's key as set-up wrote it, 2 anything else.  The
reference knows which slots a SET or an insert wrote (``Table.written``),
so it expects class 0 there and class 1 everywhere else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import U32, mix32


def _words(key, salt, n_words: int):
    """u32[n, n_words]: the record of each key."""
    row = mix32(key ^ salt)
    return mix32(row[:, None] ^ jnp.arange(n_words, dtype=U32)[None, :])


@functools.partial(jax.jit, donate_argnums=0, keep_unused=True)
def write(values, key, salt):
    """The pool's records column with every slot holding its key's record
    (in place of ``values``, which is donated; kept as an argument though
    unread, so that the output takes its buffer and the pool is never
    held twice)."""
    return _words(key, salt, values.shape[1])


@jax.jit
def classes(values, key, salt):
    """u8 per slot: 0 all zeros, 1 the record of the slot's key, 2 other."""
    zero = jnp.all(values == 0, axis=1)
    same = jnp.all(values == _words(key, salt, values.shape[1]), axis=1)
    return jnp.where(zero, 0, jnp.where(same, 1, 2)).astype(jnp.uint8)


def expected(written) -> np.ndarray:
    """The classes the reference expects from its ``written`` column."""
    return np.where(np.asarray(written), 0, 1).astype(np.uint8)


def salt(seed: int):
    return jnp.asarray(seed & 0xFFFFFFFF, U32)
