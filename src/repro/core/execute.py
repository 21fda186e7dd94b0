"""The unified execution facade (DESIGN.md §13).

One entry point replaces the ``run_trace`` / ``run_trace_grouped`` /
``dm_access``-driver sprawl:

    cache = repro.core.execute.make(cfg, n_clients)
    res = repro.core.execute(cache, keys, plan="adaptive")
    res.hit_rate, res.cache, res.windows

``plan`` selects how the [T, C] trace is scheduled:

  * ``None``        — sequential rounds (bit-identical to the legacy
                      ``run_trace``).
  * ``"strict"`` /  — one fixed-width plan from ``workloads.plan``
    ``"lane"``        (``plan_groups``; bit-identical to the legacy
                      ``run_trace_grouped`` on the same plan).
  * ``"adaptive"``  — ``plan_adaptive`` picks a group width per window
                      from the step-cost model and the hit-rate/width
                      trade, degenerating to sequential rows where
                      packing collapses.
  * a ``GroupPlan`` or ``SegmentSchedule`` — execute it as given.

Execution-time knobs (backend, max width, interpret override, buffer
donation) ride in :class:`repro.core.types.ExecConfig`; the cache's own
``CacheConfig`` keeps only semantics.  Jitted segment runners are cached
per (config, width, donation, interpret) so repeated calls pay zero
retrace; measured per-segment step times feed back into the planner's
cost model when the call compiled nothing.

Every call is observable from inside: a ``ditto.execute`` profiler span
over the whole call, with ``ditto.execute.launch`` / ``.wait`` /
``.fetch`` children tiling each segment (see :func:`_phase`), and per
segment ``windows[i]["phases_s"]`` (the same three durations),
``["compiles"]`` and ``["compile_s"]`` (backend compiles that fired
inside the call, from ``jax.monitoring``).
"""

from __future__ import annotations

import contextlib
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import (TraceResult, _run_trace_grouped_impl,
                              _run_trace_impl, make_cache)
from repro.core.types import (CacheConfig, CacheState, ClientState,
                              ExecConfig, OpStats, merge_exec_config)
from repro.kernels.runtime import force_interpret
from repro.workloads.plan import (GroupPlan, PlanCostModel, Segment,
                                  SegmentSchedule, pack_rows, plan_adaptive,
                                  plan_groups)

_UNSET = object()


class Cache(NamedTuple):
    """A cache handle: semantic config + the three state pytrees."""

    cfg: CacheConfig
    state: CacheState
    clients: ClientState
    stats: OpStats

    @property
    def n_clients(self) -> int:
        return self.clients.fc_slot.shape[0]


def make(cfg: CacheConfig, n_clients: int, seed: int = 0) -> Cache:
    """Build a fresh :class:`Cache` handle: an empty pool per ``cfg``
    plus ``n_clients`` client lanes (FC caches, expert weights, and —
    when ``cfg.l0_entries > 0`` — per-lane L0 near-caches, all empty).
    The handle is what :func:`execute` consumes and returns advanced;
    it replaces the legacy ``make_cache`` triple, which lacked the cfg.
    """
    state, clients, stats = make_cache(cfg, n_clients, seed)
    return Cache(cfg, state, clients, stats)


class ExecResult(NamedTuple):
    """Everything one execution produced: the advanced cache handle,
    per-round counters, and per-segment (window) execution metrics."""

    cache: Cache
    hits: np.ndarray           # i32[R] per executed round
    ops: np.ndarray            # i32[R]
    weights: np.ndarray        # f32[R, ...] expert-weight trajectory
    windows: Tuple[dict, ...]  # per-segment metrics: start/stop rows,
                               # width, steps, fill, wall_s, us_per_call,
                               # phases_s, compiles, compile_s
    plan_s: float              # host planning time (seconds)
    wall_s: float              # execution wall time (seconds, excludes
                               # planning)
    schedule: object           # the schedule executed (SegmentSchedule /
                               # GroupPlan / None for pure sequential)

    @property
    def cfg(self) -> CacheConfig:
        return self.cache.cfg

    @property
    def state(self) -> CacheState:
        return self.cache.state

    @property
    def clients(self) -> ClientState:
        return self.cache.clients

    @property
    def stats(self) -> OpStats:
        return self.cache.stats

    @property
    def hit_rate(self) -> float:
        from repro.core.types import hit_ratio
        return hit_ratio(self.stats)


_JIT_CACHE: dict = {}


def clear_jit_cache() -> None:
    _JIT_CACHE.clear()


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class _Compiles:
    """Backend compiles (each a compile or a load from the persistent
    cache) and their seconds, counted only while an :func:`execute` call
    is in progress.  One instance listens for the whole process,
    registered with ``jax.monitoring`` by the first call; a segment's
    count is the difference across it (calls from several threads at
    once would share it)."""

    def __init__(self):
        self.registered = False
        self.active = 0
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_):
        if self.active and event == _BACKEND_COMPILE:
            self.n += 1
            self.seconds += duration


_COMPILES = _Compiles()


@contextlib.contextmanager
def _counting_compiles():
    if not _COMPILES.registered:
        jax.monitoring.register_event_duration_secs_listener(_COMPILES)
        _COMPILES.registered = True
    _COMPILES.active += 1
    try:
        yield
    finally:
        _COMPILES.active -= 1


@contextlib.contextmanager
def _phase(name: str, window: dict):
    """One phase of a segment: a ``ditto.execute.<name>`` profiler span
    (on the trace's clock, beside the device's operations) whose
    host-clock duration is also added to ``window["phases_s"][name]``."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"ditto.execute.{name}"):
        yield
    phases = window.setdefault("phases_s", {})
    phases[name] = phases.get(name, 0.0) + time.perf_counter() - t0


def _runner(cfg: CacheConfig, grouped: bool, donate: bool,
            interpret: Optional[bool]):
    """Jitted trace runner for one (config, mode) point, cached (jit
    itself compiles once per argument shape)."""
    key = (cfg, grouped, donate, interpret)
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn
    impl = _run_trace_grouped_impl if grouped else _run_trace_impl

    def run(state, clients, stats, keys, is_write, obj_size, tenant):
        # force_interpret binds at trace time; the cache key carries the
        # flag so compiled executables never alias across overrides.
        with force_interpret(interpret):
            return impl(cfg, state, clients, keys, is_write, obj_size,
                        tenant, stats)

    fn = _JIT_CACHE[key] = jax.jit(run,
                                   donate_argnums=(0, 1) if donate else ())
    return fn


def _donate(exec_cfg: ExecConfig) -> bool:
    if exec_cfg.donate is None:
        return jax.default_backend() != "cpu"
    return exec_cfg.donate


def runner_hlo(cfg: CacheConfig, n_clients: int, rounds: int) -> str:
    """The compiled HLO text, op metadata included, of the program that
    :func:`execute` runs, with the default engine, for a sequential
    segment of ``rounds`` rounds on a cache of ``cfg`` with ``n_clients``
    lanes.  A TPU profiler trace names each device operation by its HLO
    instruction only; this text gives each instruction its ``op_name``,
    whose ``ditto.*`` component is the ``access_group`` stage it belongs
    to (DESIGN.md §13).  Nothing is allocated on the device; with the
    persistent compilation cache on, the program is loaded rather than
    compiled again."""
    exec_cfg = cfg.split()[1]
    fn = _runner(merge_exec_config(cfg, exec_cfg), False, _donate(exec_cfg),
                 exec_cfg.interpret)
    trace = [jax.ShapeDtypeStruct((rounds, n_clients), dt)
             for dt in (jnp.uint32, jnp.bool_, jnp.uint32, jnp.uint32)]
    fresh = jax.eval_shape(lambda: make_cache(cfg, n_clients, 0))
    # The calls after the first take the runner's own outputs: lower at
    # those types, the program the steady state runs.
    out = jax.eval_shape(fn, *fresh, *trace)
    return fn.lower(out.state, out.clients, out.stats,
                    *trace).compile().as_text()


def _trace_sharding(mesh):
    """A [T, S * lanes] request tensor split by source shard: each chip
    holds the lanes of its own shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dm.sharded_cache import AXIS
    return NamedSharding(mesh, P(None, AXIS))


def cluster_runner_hlo(cfg: CacheConfig, n_shards: int,
                       lanes_per_shard: int, rounds: int) -> str:
    """:func:`runner_hlo` for a :class:`repro.dm.Cluster`: the compiled
    HLO text, op metadata included, of the program :func:`execute` runs
    for a call of ``rounds`` rounds of reads and writes on a cluster of
    ``cfg`` (the GLOBAL pool) over ``n_shards`` shards with
    ``lanes_per_shard`` lanes each, under the default execution knobs.
    Besides the five ``access_group`` stages, its ``op_name`` components
    name the router's own: ``ditto.route`` (packing requests per
    destination, unpacking them, merging the answers back),
    ``ditto.exchange`` (the ``all_to_all`` collectives) and
    ``ditto.sync`` (the cross-shard weight sync).  Nothing is allocated
    on the devices."""
    import jax.sharding as jsh

    from repro.dm.cluster import runner as cluster_runner
    from repro.dm.sharded_cache import (AXIS, _dm_init_jit,
                                        identity_membership)
    xc = cfg.split()[1]
    mesh, local, build = _dm_init_jit(cfg, n_shards, lanes_per_shard)
    fn = cluster_runner(mesh, local, xc.route_factor, _donate(xc))
    req = [jax.ShapeDtypeStruct((rounds, n_shards * lanes_per_shard), dt,
                                sharding=_trace_sharding(mesh))
           for dt in (jnp.uint32, jnp.bool_)]
    def placed(tree, spec):
        sh = jsh.NamedSharding(mesh, spec)
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            tree)
    member = placed(jax.eval_shape(
        lambda: identity_membership(n_shards, cfg.n_buckets)),
        jsh.PartitionSpec())
    # The calls after the first take the runner's own outputs, which
    # keep the pool's layout: every array split over the shards.
    dm = placed(jax.eval_shape(build), jsh.PartitionSpec(AXIS))
    return fn.lower(dm, *req, None, None,
                    member=member).compile().as_text()


def _as_cache(cache) -> Cache:
    if isinstance(cache, Cache):
        return cache
    if isinstance(cache, tuple) and len(cache) == 4:
        return Cache(*cache)
    got = (f"a {len(cache)}-tuple (legacy make_cache() returns "
           "(state, clients, stats) without the cfg — build the handle "
           "with repro.core.make(cfg, n_clients) instead)"
           if isinstance(cache, tuple) else repr(type(cache)))
    raise TypeError(
        "execute() needs a repro.core.execute.Cache handle (or a "
        f"(cfg, state, clients, stats) tuple); got {got}")


def _schedule_for(plan, keys, run_cfg: CacheConfig, xc: ExecConfig,
                  is_write, sizes, tenants,
                  model: Optional[PlanCostModel]) -> Tuple[object, float]:
    """Resolve the ``plan`` argument into a SegmentSchedule + plan time."""
    T = keys.shape[0]
    # Explicit schedules are honored unconditionally (batch only caps
    # the *planner*, never a plan the caller already built).
    if isinstance(plan, SegmentSchedule):
        return plan, plan.plan_s
    if isinstance(plan, GroupPlan):
        rows = plan.n_groups * plan.batch
        sched = SegmentSchedule((Segment(0, rows, plan.batch, plan),),
                                np.full(1, plan.batch, np.int32),
                                max(rows, 1), 0.0)
        return sched, 0.0
    if plan is None or T == 0 or xc.batch <= 1:
        seg = (Segment(0, T, 1, None),) if T else ()
        return SegmentSchedule(seg, np.ones(0, np.int32), max(T, 1), 0.0), 0.0
    if plan == "adaptive":
        sched = plan_adaptive(
            keys, run_cfg.n_buckets, xc.batch, is_write=is_write,
            sizes=sizes, tenants=tenants, window=xc.window, model=model,
            capacity=run_cfg.capacity)
        return sched, sched.plan_s
    if plan in ("strict", "lane"):
        t0 = time.perf_counter()
        if plan == "lane":
            gp = pack_rows(keys, run_cfg.n_buckets, xc.batch,
                           is_write=is_write, sizes=sizes, tenants=tenants)
        else:
            gp = plan_groups(keys, run_cfg.n_buckets, xc.batch, scope=plan,
                             is_write=is_write, sizes=sizes, tenants=tenants)
        plan_s = time.perf_counter() - t0
        rows = gp.n_groups * gp.batch
        return SegmentSchedule((Segment(0, T, gp.batch, gp),),
                               np.full(1, gp.batch, np.int32),
                               max(T, 1), plan_s), plan_s
    raise ValueError(f"unknown plan mode {plan!r}")


def _execute_cluster(cluster, trace, *, plan, exec_cfg, is_write, sizes,
                     tenants) -> ExecResult:
    """Cluster branch of :func:`execute`: one pipelined, failover-aware
    ``dm_execute`` scan under the handle's membership (replica fan-out,
    re-routes and dead-shard bounces all ride the routing maps, which
    stay on the chips: ``Cluster.routes``).  Each request tensor goes
    straight to the chips of the shards whose lanes it holds.  The DM
    router packs per-destination groups itself, so host-side planning
    does not apply — ``plan`` must be left unset/None."""
    from repro.dm.cluster import runner as cluster_runner
    if plan is not _UNSET and plan is not None:
        raise ValueError(
            "execute(Cluster, ...) runs the pipelined DM scan — the "
            "router packs per-destination request groups itself; pass "
            "plan=None (or omit it)")
    xc = exec_cfg if exec_cfg is not None else cluster.cfg.split()[1]
    keys = np.asarray(trace, np.uint32)
    if keys.ndim != 2:
        raise ValueError(f"trace must be [T, S*lanes]; got {keys.shape}")
    T, L = keys.shape
    if L % cluster.n_shards != 0:
        raise ValueError(
            f"trace width {L} not divisible by n_shards={cluster.n_shards}")

    win = dict(start=0, stop=T, width=1)
    n0, s0 = _COMPILES.n, _COMPILES.seconds
    with _phase("launch", win):
        fn = cluster_runner(cluster.mesh, cluster.local, xc.route_factor,
                            _donate(xc))
        on_chips = lambda x, dt: None if x is None else jax.device_put(
            np.asarray(x, dt), _trace_sharding(cluster.mesh))
        t0 = time.perf_counter()
        dm, hits = fn(cluster.dm, on_chips(keys, np.uint32),
                      on_chips(is_write, bool), on_chips(sizes, np.uint32),
                      on_chips(tenants, np.uint32), member=cluster.routes)
    with _phase("wait", win):
        hits = jax.block_until_ready(hits)
    wall = time.perf_counter() - t0
    with _phase("fetch", win):
        hits = np.asarray(hits, bool)

    new_cluster = cluster._replace(dm=dm)
    ops = (keys != 0).sum(axis=1).astype(np.int32)
    n_req = int(ops.sum())
    win.update(n_steps=T, n_requests=n_req, fill=1.0, wall_s=wall,
               us_per_call=wall * 1e6 / max(n_req, 1),
               compiles=_COMPILES.n - n0,
               compile_s=_COMPILES.seconds - s0)
    return ExecResult(new_cluster, hits.sum(axis=1).astype(np.int32), ops,
                      np.zeros((0,), np.float32), (win,), 0.0, wall, None)


def execute(cache, trace, *, plan=_UNSET, exec_cfg: ExecConfig | None = None,
            is_write=None, sizes=None, tenants=None,
            model: Optional[PlanCostModel] = None) -> ExecResult:
    """Execute a [T, C] request trace against a cache, planned.

    Args:
      cache: :class:`Cache` handle (or (cfg, state, clients, stats)) —
        or a :class:`repro.dm.Cluster`, in which case the trace is
        [T, n_shards*lanes] and runs as one failover-aware pipelined DM
        scan under the cluster's membership (see `_execute_cluster`).
      trace: u32[T, C] keys; 0 marks a padded no-op lane.
      plan: ``"adaptive" | "strict" | "lane" | None``, or a precomputed
        ``GroupPlan`` / ``SegmentSchedule``.  Defaults to
        ``exec_cfg.plan`` (itself defaulting to ``"adaptive"``).
      exec_cfg: execution-time knobs (:class:`ExecConfig`); ``None``
        derives one from the cache config's legacy ``backend`` field —
        the compat shim under which pre-split configs run bit-identical.
      is_write / sizes / tenants: optional [T, C] op tensors.
      model: optional :class:`PlanCostModel` shared across calls so
        measured step times refine the planner's width decisions online.

    Returns an :class:`ExecResult`.  ``hits``/``ops`` are per *executed
    round* (planned segments execute the plan's round order, sequential
    segments the trace's); totals in ``stats`` are order-free.
    """
    from repro.dm.cluster import Cluster
    with jax.profiler.TraceAnnotation("ditto.execute"), _counting_compiles():
        if isinstance(cache, Cluster):
            return _execute_cluster(cache, trace, plan=plan,
                                    exec_cfg=exec_cfg, is_write=is_write,
                                    sizes=sizes, tenants=tenants)
        return _execute_cache(cache, trace, plan=plan, exec_cfg=exec_cfg,
                              is_write=is_write, sizes=sizes,
                              tenants=tenants, model=model)


def _execute_cache(cache, trace, *, plan, exec_cfg, is_write, sizes, tenants,
                   model) -> ExecResult:
    """The single-pool branch of :func:`execute`: the planned segments,
    each one jitted runner call (launch, wait, fetch)."""
    cache = _as_cache(cache)
    if exec_cfg is None:
        exec_cfg = cache.cfg.split()[1]
    run_cfg = merge_exec_config(cache.cfg, exec_cfg)
    if plan is _UNSET:
        plan = exec_cfg.plan

    keys = np.asarray(trace, np.uint32)
    if keys.ndim != 2:
        raise ValueError(f"trace must be [T, C]; got shape {keys.shape}")
    T, C = keys.shape
    is_write_np = None if is_write is None else np.asarray(is_write, bool)
    sizes_np = None if sizes is None else np.asarray(sizes, np.uint32)
    tenants_np = None if tenants is None else np.asarray(tenants, np.uint32)

    sched, plan_s = _schedule_for(plan, keys, run_cfg, exec_cfg,
                                  is_write_np, sizes_np, tenants_np, model)

    donate = _donate(exec_cfg)

    state, clients, stats = cache.state, cache.clients, cache.stats
    hits_parts, ops_parts, w_parts, windows = [], [], [], []
    wall_total = 0.0

    def _slice(arr, default, s: Segment):
        if arr is None:
            return default
        return jnp.asarray(arr[s.start:s.stop])

    for seg in sched.segments:
        rows = seg.stop - seg.start
        if rows <= 0:
            continue
        grouped = seg.width > 1
        win = dict(start=seg.start, stop=seg.stop, width=seg.width)
        n0, s0 = _COMPILES.n, _COMPILES.seconds
        with _phase("launch", win):
            fn = _runner(run_cfg, grouped, donate, exec_cfg.interpret)
            if grouped:
                gp = seg.plan
                args = (jnp.asarray(gp.keys), jnp.asarray(gp.is_write),
                        jnp.asarray(gp.sizes),
                        jnp.zeros(gp.keys.shape, jnp.uint32)
                        if gp.tenants is None else jnp.asarray(gp.tenants))
                n_req = gp.n_scheduled
                n_steps = gp.n_groups
                fill = gp.fill
            else:
                k = jnp.asarray(keys[seg.start:seg.stop])
                args = (k,
                        _slice(is_write_np, jnp.zeros((rows, C), bool), seg),
                        _slice(sizes_np, jnp.ones((rows, C), jnp.uint32),
                               seg),
                        _slice(tenants_np, jnp.zeros((rows, C), jnp.uint32),
                               seg))
                n_req = int((keys[seg.start:seg.stop] != 0).sum())
                n_steps = rows
                fill = 1.0
            t0 = time.perf_counter()
            res: TraceResult = fn(state, clients, stats, *args)
        with _phase("wait", win):
            res = jax.block_until_ready(res)
        wall = time.perf_counter() - t0
        wall_total += wall
        with _phase("fetch", win):
            state, clients, stats = res.state, res.clients, res.stats
            hits_parts.append(np.asarray(res.hits))
            ops_parts.append(np.asarray(res.ops))
            w_parts.append(np.asarray(res.weights))
        compiles = _COMPILES.n - n0
        win.update(n_steps=n_steps, n_requests=n_req,
                   fill=round(float(fill), 4), wall_s=wall,
                   us_per_call=wall * 1e6 / max(n_req, 1),
                   compiles=compiles, compile_s=_COMPILES.seconds - s0)
        windows.append(win)
        # Only timings of calls that compiled nothing teach the cost model
        # (compiles would dwarf the signal and freeze the planner at G=1
        # forever).  Packing efficiency rides along so the planner's
        # optimistic prune knows how much of each group was padding on
        # THIS trace shape.
        if model is not None and compiles == 0 and n_steps > 0:
            model.observe(seg.width, wall * 1e6 / n_steps,
                          eff=rows / (n_steps * seg.width))

    new_cache = Cache(cache.cfg, state, clients, stats)
    hits = np.concatenate(hits_parts) if hits_parts else np.zeros(0, np.int32)
    ops = np.concatenate(ops_parts) if ops_parts else np.zeros(0, np.int32)
    weights = (np.concatenate(w_parts)
               if w_parts else np.zeros((0,), np.float32))
    return ExecResult(new_cache, hits, ops, weights, tuple(windows),
                      plan_s, wall_total, sched)
