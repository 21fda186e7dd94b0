"""The profiler trace of a traced run, and its reduction to numbers.

``span(name)`` marks what the benchmark's host side is doing (its spans
are ``bench.*``: the slice of the trace, the ``execute`` call, the
bookkeeping after it); they land in the profiler's trace beside the
device's operations.  ``Tracer`` records the first calls of a traced
window and reads the trace into a ``Summary``: the host spans and, per
device plane, the operations and the compiled programs (modules) that ran.
Everything after that is arithmetic on intervals, kept here so that every
PR computes the same numbers in the same way.
"""

from __future__ import annotations

import re
import shutil
from dataclasses import dataclass, field
from pathlib import Path

import jax

HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)      # (name, start_ns, dur_ns)
    modules: list = field(default_factory=list)  # (name, start_ns, dur_ns)


@dataclass
class Summary:
    spans: list                  # (name, start_ns, end_ns), host spans
    devices: list                # Device per device plane with events
    host: list = field(default_factory=list)  # every host event, as spans

    def window(self, name: str = HOST_PREFIX + "execute"):
        """From the first to the last end of the named host spans."""
        s = [x for x in self.spans if x[0] == name]
        if not s:
            return None
        return min(x[1] for x in s), max(x[2] for x in s)

    def busy(self, dev: Device, t0: int, t1: int) -> int:
        """Nanoseconds in [t0, t1] in which some operation ran on dev."""
        ev = dev.ops or dev.modules
        return sum(e - s for s, e in merge(ev, t0, t1))

    def module_ns(self, dev: Device, match, t0: int, t1: int) -> int:
        """Device time of the modules whose name satisfies ``match``."""
        return sum(e - s for s, e in merge(
            [m for m in dev.modules if match(m[0])], t0, t1))

    def runner_ns(self, names) -> int:
        """Device time in the window of the modules whose names start with
        one of ``names``, on the chip where they take most.  A trace whose
        device planes hold modules but none of these names is an error:
        the program's runner has another name than the configuration
        gives, and a metric of it would go silent."""
        t0, t1 = self.window()
        names = tuple(names)
        ns = [self.module_ns(d, lambda n: n.startswith(names), t0, t1)
              for d in self.devices]
        if not any(ns) and any(d.modules for d in self.devices):
            found = sorted({m[0] for d in self.devices for m in d.modules})
            raise LookupError(
                f"no device module named {', '.join(names)} in the traced "
                f"window; the trace holds {', '.join(found[:10])}")
        return max(ns, default=0)

    def op_ns(self, dev: Device, match, t0: int, t1: int) -> int:
        return sum(e - s for s, e in merge(
            [o for o in dev.ops if match(o[0])], t0, t1))

    def top_ops(self, dev: Device, n: int = 10) -> list:
        """The n operations with the most device time, by short name (a
        loop's own time includes the operations inside it)."""
        tot = {}
        for name, _, d in dev.ops:
            name = short_name(name)
            tot[name] = tot.get(name, 0) + d
        return sorted(tot.items(), key=lambda x: -x[1])[:n]

    def idle_gaps(self, dev: Device, t0: int, t1: int, n: int = 10) -> list:
        """The n longest idle gaps in [t0, t1], each named by the
        innermost benchmark span that covers its middle and, after a
        ``>``, by the innermost host event there (what the host was
        doing)."""
        busy = merge(dev.ops or dev.modules, t0, t1)
        gaps, last = [], t0
        for s, e in busy:
            if s > last:
                gaps.append((last, s))
            last = max(last, e)
        if t1 > last:
            gaps.append((last, t1))
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = (s + e) // 2
            name = _innermost(self.spans, mid) or "no benchmark span"
            inner = _innermost(
                [x for x in self.host if not x[0].startswith(HOST_PREFIX)],
                mid)
            out.append((f"{name} > {inner}" if inner else name, e - s))
        return out


def short_name(op: str) -> str:
    """``%fusion.7 = u32[8388608]{0:T(1024)} fusion(...)`` -> ``%fusion.7
    u32[8388608]``: the HLO name and its array shape, without layout."""
    m = re.match(r"(%[\w.\-]+) = (\w+\[[\d,]*\])?", op)
    if not m:
        return op[:80]
    return " ".join(x for x in m.groups() if x)


def _innermost(spans, t: int):
    cover = [x for x in spans if x[1] <= t <= x[2]]
    return min(cover, key=lambda x: x[2] - x[1])[0] if cover else None


def merge(events, t0: int, t1: int) -> list:
    """Union of the events' intervals, clipped to [t0, t1]."""
    iv = sorted((max(s, t0), min(s + d, t1)) for _, s, d in events
                if s < t1 and s + d > t0)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def read(path: Path) -> Summary:
    """Reduce one ``.xplane.pb`` file to a Summary."""
    data = jax.profiler.ProfileData.from_file(str(path))
    host, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = [(e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events]
                elif line.name == MODULES_LINE:
                    dev.modules = [(e.name, int(e.start_ns),
                                    int(e.duration_ns)) for e in line.events]
            if dev.ops or dev.modules:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                         for e in line.events]
    spans = [x for x in host if x[0].startswith(HOST_PREFIX)]
    return Summary(spans=spans, devices=devices, host=host)


class Tracer:
    """Profiles the calls between ``start`` and ``stop`` into ``where``
    (emptied first and removed once read)."""

    def __init__(self, where: Path):
        self.where = Path(where)
        self.active = False
        self._summary = None

    def start(self):
        shutil.rmtree(self.where, ignore_errors=True)
        self.where.mkdir(parents=True)
        jax.profiler.start_trace(str(self.where))
        self.active = True

    def stop(self):
        jax.profiler.stop_trace()
        self.active = False

    def summary(self) -> Summary:
        if self._summary is None:
            files = sorted(self.where.glob("**/*.xplane.pb"))
            if not files:
                raise FileNotFoundError(f"no trace under {self.where}")
            self._summary = read(files[-1])
            shutil.rmtree(self.where, ignore_errors=True)
        return self._summary
