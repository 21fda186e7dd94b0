"""Backend-dependent execution defaults and table layout shared by every
Pallas kernel.

The kernels run through the Pallas interpreter on CPU/GPU hosts and as
compiled Mosaic kernels on TPU.  Each kernel signature takes
``interpret: bool | None = None`` and resolves ``None`` through
:func:`interpret_default` at trace time — so a TPU caller that forgets
to thread the flag gets the compiled kernel, never a silent interpreter
fallback (dittolint rule DL005 enforces that no signature hard-codes
``interpret=True`` outside tests).

Table layout: the cache keeps every slot column as a flat ``u32[n]``.
The kernels see it as ``i32[n / 128, 128]`` (a bitcast plus a reshape,
no arithmetic), so one 8-slot bucket is a lane range of one row and a
sampling window is a lane range of at most two consecutive rows — both
read with a scalar row index, the access Mosaic supports.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

LANES = 128

# Scoped-VMEM budget of the table-resident kernels (access_probe,
# ranked_eviction).  A TPU v5e core has 128 MiB of VMEM; the rest is
# headroom for the compiler's own buffers.
VMEM_LIMIT_BYTES = 100 * 2**20

# Largest pool (slots) the fused backend accepts: the tables the widest
# kernel holds in VMEM (ranked_eviction with a tenant column: 5 i32
# columns, 20 B/slot) must fit VMEM_LIMIT_BYTES with room for its
# per-block scratch.  2**22 slots = 80 MiB of tables; 2**23 does not fit.
FUSED_MAX_SLOTS = 2**22

# Session-scoped override installed by ExecConfig.interpret (DESIGN.md
# §13): callers that cannot thread the flag through every kernel
# signature (the execute() facade jits whole trace drivers) set it for
# the duration of a trace instead.  None = no override.
_OVERRIDE: bool | None = None


def interpret_default() -> bool:
    """True off-TPU (interpreter), False on TPU (compiled Mosaic)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """Resolve a kernel's ``interpret`` argument: ``None`` -> the active
    :func:`force_interpret` override, else the backend default.  Called
    inside jitted kernels; ``interpret`` is static, so this runs at
    trace time and costs nothing at runtime."""
    if interpret is not None:
        return bool(interpret)
    if _OVERRIDE is not None:
        return _OVERRIDE
    return interpret_default()


@contextlib.contextmanager
def force_interpret(flag: bool | None):
    """Trace-time override of every ``interpret=None`` kernel default.

    ``None`` is a no-op context.  Callers jitting under the override
    must key their jit caches on the flag: it binds at trace time."""
    global _OVERRIDE
    prev = _OVERRIDE
    _OVERRIDE = None if flag is None else bool(flag)
    try:
        yield
    finally:
        _OVERRIDE = prev


def auto_block_b(n: int, interpret: bool) -> int:
    """Request-tile width for a batch of ``n``: a multiple of 8 (the
    sublane tile of the kernels' [block_b, 1] request columns).  The
    interpreter widens it with the batch, up to 256 (per-grid-cell
    overhead dominates there); compiled kernels keep 128, whose
    per-request row gathers are a scalar loop either way."""
    n8 = -(-max(n, 1) // 8) * 8
    return min(256 if interpret else 128, n8)


def as_i32(x) -> jnp.ndarray:
    """The i32 bit pattern of a u32 value (integral values of any other
    dtype are cast to u32 first)."""
    return jax.lax.bitcast_convert_type(jnp.asarray(x).astype(jnp.uint32),
                                        jnp.int32)


def as_rows(col: jnp.ndarray) -> jnp.ndarray:
    """u32[n] slot column -> i32[n / 128, 128] kernel table."""
    if col.shape[0] % LANES:
        raise ValueError(f"table of {col.shape[0]} slots is not a multiple "
                         f"of {LANES}")
    return as_i32(col).reshape(-1, LANES)


def as_column(x: jnp.ndarray, n: int, fill=0) -> jnp.ndarray:
    """[B] per-request values -> [n, 1] (padded with ``fill``)."""
    x = jnp.asarray(x)
    x = jnp.concatenate([x, jnp.full((n - x.shape[0],), fill, x.dtype)])
    return x.reshape(n, 1)


def u32_to_f32(x: jnp.ndarray) -> jnp.ndarray:
    """Exact ``u32 -> f32`` conversion of an i32-bitcast u32 value, built
    from two exact 16-bit halves so the one rounding is the final add —
    the same correctly rounded result XLA's unsigned convert gives."""
    hi = jax.lax.shift_right_logical(x, jnp.int32(16))
    lo = x & jnp.int32(0xFFFF)
    return hi.astype(jnp.float32) * 65536.0 + lo.astype(jnp.float32)
