"""The closed loop every system's cell runs, and the numbers that decide
``correct``.

A system module builds its handle (pool, lanes) and its traffic, then
calls ``serve``: the warm-up calls, then the window.  Each call carries
``rounds_per_call`` rounds of one request per lane; the next call goes out
when the previous one has returned its answers.  A traced run profiles
the window's first ``trace_calls`` calls.  The system then reads its
handle back, frees it, replays the run through the plain reference and
hands both accounts to ``compare``.
"""

from __future__ import annotations

import gc
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from bench.reference import COUNTERS, FIELDS as COLUMNS
from bench.tracing import Tracer, span

SCALARS = ("n_cached", "bytes_cached", "hist_ctr", "clock")


class OutOfTrace(Exception):
    """The window would need more requests than the trace holds."""


@dataclass
class Outcome:
    """What one run measured; the metric readers read it."""

    setup_s: float
    window_s: float
    latencies_s: list
    requests_per_call: int
    hit_rate: float
    peak_bytes: int
    counters: dict            # program counters over the window
    attempted: int
    failed: int
    config: dict
    traffic: dict
    traced: object = None     # tracing.Summary of the traced calls
    traced_counters: dict = field(default_factory=dict)
    traced_rounds: int = 0
    checks: dict = field(default_factory=dict)
    peaks: dict = None        # the device's row of bench/peaks.json

    @property
    def completed(self) -> int:
        return len(self.latencies_s) * self.requests_per_call


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Phases:
    """Host-clock times of a run's phases, printed on standard error."""

    def __init__(self):
        self.t = time.perf_counter()

    def done(self, what: str) -> None:
        now = time.perf_counter()
        log(f"{what} {now - self.t:.3f} s")
        self.t = now


class Compiles:
    """Counts JAX's compiles and traces while active (nothing should
    compile inside the window)."""

    def __init__(self):
        self.compiles, self.traces, self.seconds = 0, 0, 0.0
        self.on = False

    def __call__(self, event: str, duration: float, **_):
        if not self.on:
            return
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
            self.seconds += duration
        elif event.endswith("jaxpr_trace_duration"):
            self.traces += 1


class Pauses:
    """Time the Python collector takes while active.  Set-up's objects
    are frozen out of its scans before the window (``gc.freeze``): they
    live for the whole run, and a full scan of them stalls a call by
    about 0.1 s."""

    def __init__(self):
        self.n, self.seconds, self._t = 0, 0.0, None
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: dict):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.seconds += time.perf_counter() - self._t
            self._t = None

    def close(self):
        gc.callbacks.remove(self)


def counters(stats) -> dict:
    return {k: int(getattr(stats, k)) for k in COUNTERS + ("route_drops",)}


def _delta(a: dict, b: dict) -> dict:
    return {k: a[k] - b[k] for k in a}


def serve(job, t_start: float, handle, execute, tr):
    """Warm-up and window on ``handle`` through ``execute``.  Returns the
    final handle, the Outcome, the hit count of every round served (warm-up
    included) and the number of rounds served."""
    import jax
    rpc, rows = tr.rounds_per_call, tr.keys.shape[0]
    lanes = tr.keys.shape[1]
    hits, row = [], 0
    for _ in range(tr.warmup_calls):
        res = execute(handle, tr.keys[row:row + rpc],
                      is_write=tr.writes[row:row + rpc], plan=None)
        hits.append(res.hits)
        handle, row = res.cache, row + rpc
    before = counters(handle.stats)
    window_row = row
    log(f"warm-up: {tr.warmup_calls} calls")

    tracer = Tracer(job.scratch) if job.trace else None
    if tracer:
        tracer.start()
    traced = before
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    compiles.on = True
    pauses = Pauses()
    lat, slow = [], []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    deadline = t0 + job.seconds
    while time.perf_counter() < deadline:
        if row + rpc > rows:
            raise OutOfTrace(f"the window needed more than the {rows} "
                             "rounds the trace holds")
        with span("bench.slice"):
            k, w = tr.keys[row:row + rpc], tr.writes[row:row + rpc]
        c0, g0 = time.perf_counter(), pauses.seconds
        with span("bench.execute"):
            res = execute(handle, k, is_write=w, plan=None)
        lat.append(time.perf_counter() - c0)
        if lat[-1] > 0.1:
            slow.append((len(lat) - 1, lat[-1], pauses.seconds - g0))
        with span("bench.record"):
            hits.append(res.hits)
            handle, row = res.cache, row + rpc
        if tracer and tracer.active and len(lat) == tr.trace_calls:
            traced = counters(handle.stats)
            s0 = time.perf_counter()
            tracer.stop()
            # collecting the trace is no part of the window's work
            deadline += time.perf_counter() - s0
    window_s = time.perf_counter() - t0
    compiles.on = False
    jax.monitoring.unregister_event_duration_listener(compiles)
    pauses.close()
    log(f"set-up {setup_s:.3f} s; window {window_s:.3f} s, {len(lat)} calls,"
        f" slowest {max(lat, default=0) * 1e3:.3f} ms, "
        f"{len(slow)} over 100 ms; {compiles.compiles} "
        f"compiles ({compiles.seconds:.3f} s) and {compiles.traces} traces "
        f"in the window; {pauses.n} collections ({pauses.seconds:.3f} s)")
    if slow:
        log("calls over 100 ms (call, ms, of it collecting): " + ", ".join(
            f"({i}, {t * 1e3:.1f}, {g * 1e3:.1f})" for i, t, g in slow[:10]))
    if tracer and tracer.active:
        traced = counters(handle.stats)
        tracer.stop()

    hits = np.concatenate([np.asarray(h) for h in hits])
    window_hits = hits[window_row:]
    need = -(-tr.hit_rate_ops // lanes)
    if len(window_hits) < need:
        raise OutOfTrace(f"the window ran {len(window_hits)} rounds; "
                         f"hit_rate needs {need}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    c = _delta(counters(handle.stats), before)
    attempted = (row - window_row) * lanes
    out = Outcome(
        setup_s=setup_s, window_s=window_s, latencies_s=lat,
        requests_per_call=rpc * lanes,
        hit_rate=float(window_hits[:need].sum()) / (need * lanes),
        peak_bytes=int(peak), counters=c, attempted=attempted,
        failed=attempted - (c["gets"] + c["sets"]),
        config=job.config, traffic=job.traffic)
    if tracer:
        out.traced = tracer.summary()
        out.traced_counters = _delta(traced, before)
        out.traced_rounds = min(tr.trace_calls, len(lat)) * rpc
    return handle, out, hits, row


def compare(got: dict, want: dict, issued: int, limits: dict) -> dict:
    """The numbers that decide ``correct``, each beside its limit.  Both
    accounts hold the table's columns, the pool's scalars, the global and
    lane expert weights, the class of every slot's record, the hit count
    of every round and the operation counters.

    - hits_gap: over every round, |hits of the program - of the reference|;
    - slots_gap: slots that differ in any column of the pool's table;
    - weights_gap: the largest gap of a global or lane expert weight;
    - counters_gap: summed |gap| of the operation counters;
    - occupancy_gap: |gap| of the object count, the byte count, the
      history counter and the clock;
    - records_gap: slots whose record is not what the reference expects
      (``bench/records.py``): zeros where a SET or an insert wrote it,
      else the record set-up gave its key;
    - issued_gap: |gets + sets + route_drops - requests issued|."""
    hits_gap = sum(int(np.abs(np.asarray(got[h], np.int64)
                              - np.asarray(want[h], np.int64)).sum())
                   for h in ("fill_hits", "lane_hits"))
    differ = np.zeros(np.shape(got["key"]), bool)
    for col in COLUMNS:
        differ |= np.asarray(got[col]) != np.asarray(want[col])
    weights_gap = max(
        float(np.max(np.abs(np.asarray(got[w], np.float64)
                            - np.asarray(want[w], np.float64))))
        for w in ("weights", "local_w"))
    counters_gap = sum(abs(got["counters"][k] - want["counters"][k])
                       for k in COUNTERS)
    occupancy_gap = sum(int(np.abs(np.asarray(got[k], np.int64)
                                   - np.asarray(want[k], np.int64)).sum())
                        for k in SCALARS)
    c = got["counters"]
    values = dict(hits_gap=hits_gap, slots_gap=int(differ.sum()),
                  weights_gap=weights_gap, counters_gap=int(counters_gap),
                  occupancy_gap=occupancy_gap,
                  records_gap=int(np.sum(got["record_class"]
                                         != want["record_class"])),
                  issued_gap=abs(c["gets"] + c["sets"] + c["route_drops"]
                                 - issued))
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def read_back(handle) -> dict:
    """The program's account: its table, scalars, weights and counters."""
    st, cl = handle.state, handle.clients
    out = {c: np.asarray(getattr(st, c)) for c in COLUMNS + SCALARS}
    out.update(weights=np.asarray(st.weights),
               local_w=np.asarray(cl.local_weights),
               counters=counters(handle.stats))
    return out
