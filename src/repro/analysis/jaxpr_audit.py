"""dittolint pass 2: closed-jaxpr audit of the real cache entry points.

The AST pass sees what the *source* says; this pass sees what jax will
actually *execute*.  It traces the production entry points — ``access``,
``access_group``, ``run_trace_grouped``, ``dm_access``,
``ranked_eviction`` — across backend x width x tenant configs and walks
the closed jaxprs (recursively through scan/pjit/shard_map bodies):

  JX001  64-bit dtype produced in a traced hot path (f64/i64/u64 eqn
         output — a silent 2x memory/bandwidth tax on TPU).
  JX002  ``convert_element_type`` churn: an A->B->A round-trip convert
         chain, or total converts above the entry point's budget
         (CONVERT_BUDGETS — calibrated to the shipped tree, headroom
         included; creep past it means a new conversion hotspot).
  JX003  host callback (``debug_print``/``io_callback``/
         ``pure_callback``) in a hot path — each one is a device->host
         sync that serializes the step.
  JX004  dead output: an entry-point output that is a trace-time
         literal or does not depend on any input (computed, shipped,
         never meaningful).
  JX005  jit retrace budget: compiling more entries than distinct shape
         signatures (weak-type/dtype flapping — every silent retrace is
         a multi-second stall on the batching-cliff path).

Pure jaxpr inspection — nothing here executes kernels except the JX005
probe, which runs tiny configs.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Iterator, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax._src import core as jax_core

RULES: Dict[str, str] = {
    "JX001": "64-bit dtype produced in a traced hot path (f64/i64/u64)",
    "JX002": "convert_element_type churn (A->B->A round-trip or budget "
             "exceeded)",
    "JX003": "host callback (debug_print/io_callback/pure_callback) in a "
             "hot-path jaxpr",
    "JX004": "dead output: entry-point output is a literal or independent "
             "of every input",
    "JX005": "jit retrace budget exceeded (more compiles than distinct "
             "shape signatures)",
}

_WIDE = frozenset({"float64", "int64", "uint64"})

# Total convert_element_type budgets per entry point: the shipped tree's
# measured counts (~130 for the core step, ~8 for the kernel) plus ~50%
# headroom.  Budget creep is a review decision, not a silent drift.
CONVERT_BUDGETS: Dict[str, int] = {
    "access": 200,
    "access_group": 200,
    "run_trace_grouped": 220,
    "dm_access": 400,
    "ranked_eviction": 40,
}


class Finding(NamedTuple):
    rule: str
    entry: str
    msg: str

    def __str__(self) -> str:
        return f"{self.entry}: {self.rule} {self.msg}"


def _src_line(eqn) -> str:
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown>"


def iter_eqns(jaxpr) -> Iterator:
    """All equations of a jaxpr, recursing into sub-jaxprs (scan bodies,
    pjit/shard_map calls, cond branches, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            sub = []
            if hasattr(p, "jaxpr"):
                sub = [p.jaxpr if hasattr(p.jaxpr, "eqns") else p]
            elif isinstance(p, (list, tuple)):
                sub = [q.jaxpr for q in p if hasattr(q, "jaxpr")]
            for s in sub:
                if hasattr(s, "eqns"):
                    yield from iter_eqns(s)


def audit_closed(closed, entry: str,
                 convert_budget: Optional[int] = None) -> List[Finding]:
    """Audit one ClosedJaxpr against JX001-JX004."""
    jaxpr = closed.jaxpr
    findings: List[Finding] = []
    producer: Dict = {}
    n_convert = 0
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        # JX003: host callbacks.
        if "callback" in name or name == "debug_print":
            findings.append(Finding(
                "JX003", entry, f"'{name}' at {_src_line(eqn)}"))
        # JX001: wide dtypes.
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and str(dt) in _WIDE:
                findings.append(Finding(
                    "JX001", entry,
                    f"'{name}' produces {dt} at {_src_line(eqn)}"))
        # JX002: convert round-trips (A -> B -> A with the middle hop
        # produced by another convert).
        if name == "convert_element_type":
            n_convert += 1
            iv = eqn.invars[0]
            src = producer.get(iv) if not isinstance(iv, jax_core.Literal) \
                else None
            if src is not None and src.primitive.name == \
                    "convert_element_type":
                inner = src.invars[0]
                in_dt = getattr(getattr(inner, "aval", None), "dtype", None)
                if in_dt is not None and \
                        in_dt == eqn.outvars[0].aval.dtype:
                    findings.append(Finding(
                        "JX002", entry,
                        f"round-trip {in_dt} -> {iv.aval.dtype} -> "
                        f"{eqn.outvars[0].aval.dtype} at {_src_line(eqn)} "
                        f"(inner convert at {_src_line(src)})"))
        for v in eqn.outvars:
            producer[v] = eqn
    if convert_budget is not None and n_convert > convert_budget:
        findings.append(Finding(
            "JX002", entry,
            f"{n_convert} convert_element_type eqns > budget "
            f"{convert_budget}"))
    # JX004: dead outputs — literals, or outvars unreachable from inputs
    # (a trace-time constant shipped as a result).  Top-level only: a
    # passthrough (output == input) is legitimately input-dependent.
    reach = {v for v in jaxpr.invars}
    changed = True
    eqns = list(jaxpr.eqns)
    while changed:
        changed = False
        for eqn in eqns:
            if any(not isinstance(v, jax_core.Literal) and v in reach
                   for v in eqn.invars):
                for o in eqn.outvars:
                    if o not in reach:
                        reach.add(o)
                        changed = True
    for i, v in enumerate(jaxpr.outvars):
        if isinstance(v, jax_core.Literal):
            findings.append(Finding(
                "JX004", entry, f"output[{i}] is the literal {v.val!r}"))
        elif v not in reach:
            findings.append(Finding(
                "JX004", entry,
                f"output[{i}] ({v.aval}) does not depend on any input"))
    return findings


def count_retraces(fn: Callable, calls: List[tuple]) -> int:
    """Number of compilations a fresh ``jax.jit`` of ``fn`` performs over
    ``calls`` (each called twice — the second pass must be all hits)."""
    jf = jax.jit(fn)
    for args in calls:
        jf(*args)
    for args in calls:
        jf(*args)
    return int(jf._cache_size())


# ----------------------------------------------------------------------
# The entry-point harness: tiny configs, real code paths.
# ----------------------------------------------------------------------

def _small_cfg(backend: str, n_tenants: int, sanitize: bool = False):
    import dataclasses

    from repro.core.types import CacheConfig
    cfg = CacheConfig(n_buckets=64, assoc=4, capacity=64, hist_len=64,
                      backend=backend, n_tenants=n_tenants)
    if sanitize:
        cfg = dataclasses.replace(cfg, sanitize=True)
    return cfg


def audit_entry_points(widths=(1, 8), backends=("reference", "fused"),
                       tenants=(1, 2), n_clients: int = 4,
                       include_dm: bool = True,
                       retrace_widths=(1, 8, 32)) -> List[Finding]:
    """Trace every production entry point across backend x width x tenant
    configs and audit the closed jaxprs; then probe JX005 retrace budgets
    with live jit calls on the smallest config."""
    from repro.core.cache import access, access_group, run_trace_grouped
    from repro.core.types import init_cache, init_clients, init_stats
    from repro.kernels import ops as kops

    findings: List[Finding] = []
    for backend in backends:
        for tn in tenants:
            cfg = _small_cfg(backend, tn)
            st = init_cache(cfg)
            cl = init_clients(cfg, n_clients)
            sa = init_stats()
            ten = jnp.zeros((n_clients,), jnp.uint32)
            closed = jax.make_jaxpr(
                lambda s, c, a, k: access(cfg, s, c, a, k, tenant=ten))(
                    st, cl, sa, jnp.ones((n_clients,), jnp.uint32))
            findings += audit_closed(closed, "access",
                                     CONVERT_BUDGETS["access"])
            for g in widths:
                keys = jnp.ones((g, n_clients), jnp.uint32)
                closed = jax.make_jaxpr(
                    lambda s, c, a, k: access_group(cfg, s, c, a, k))(
                        st, cl, sa, keys)
                findings += audit_closed(closed, "access_group",
                                         CONVERT_BUDGETS["access_group"])
            closed = jax.make_jaxpr(
                lambda s, c, a, k: run_trace_grouped(cfg, s, c, k,
                                                     stats=a))(
                    st, cl, sa, jnp.ones((3, 2, n_clients), jnp.uint32))
            findings += audit_closed(closed, "run_trace_grouped",
                                     CONVERT_BUDGETS["run_trace_grouped"])

    # ranked_eviction: the fused kernel's public op wrapper.
    w, k, b, c = 20, 5, 8, 256
    col = jnp.zeros((c,), jnp.uint32)
    closed = jax.make_jaxpr(
        lambda s, i, l, f, o, e, m, q, t: kops.ranked_eviction_op(
            s, i, l, f, o, e, m, q, t, window=w, k=k))(
        col, col, col, col, jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool),
        jnp.ones((b,), jnp.int32), jnp.ones((b,), jnp.uint32))
    findings += audit_closed(closed, "ranked_eviction",
                             CONVERT_BUDGETS["ranked_eviction"])

    if include_dm:
        findings += _audit_dm()

    findings += audit_retraces(widths=retrace_widths, backends=backends)
    return findings


def _audit_dm() -> List[Finding]:
    """Audit ``dm_access`` on however many devices this process has (the
    routing/collective structure is shard-count independent)."""
    from repro.core.types import CacheConfig
    from repro.dm.sharded_cache import _dm_access_impl, _dm_make_impl
    n_shards = len(jax.devices())
    cfg = CacheConfig(n_buckets=64 * n_shards, assoc=4,
                      capacity=64 * n_shards, hist_len=64 * n_shards)
    mesh, dm, local = _dm_make_impl(cfg, n_shards=n_shards,
                                    lanes_per_shard=4)
    keys = jnp.ones((n_shards * 4,), jnp.uint32)
    closed = jax.make_jaxpr(
        functools.partial(_dm_access_impl, mesh, local))(dm, keys)
    return audit_closed(closed, "dm_access", CONVERT_BUDGETS["dm_access"])


def audit_retraces(widths=(1, 8, 32), backends=("reference", "fused"),
                   n_clients: int = 4) -> List[Finding]:
    """JX005: sweeping widths over a fixed config must compile each entry
    point exactly once per shape signature (the recompile-count budget)."""
    from repro.core.cache import access_group
    from repro.core.types import init_cache, init_clients, init_stats

    findings: List[Finding] = []
    for backend in backends:
        cfg = _small_cfg(backend, 1)
        st = init_cache(cfg)
        cl = init_clients(cfg, n_clients)
        sa = init_stats()
        calls = [(st, cl, sa, jnp.ones((g, n_clients), jnp.uint32))
                 for g in widths]
        n = count_retraces(functools.partial(access_group, cfg), calls)
        if n > len(widths):
            findings.append(Finding(
                "JX005", "access_group",
                f"{backend}: {n} compiles for {len(widths)} width "
                f"signatures {tuple(widths)}"))
    return findings
