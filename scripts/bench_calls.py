"""Run one benchmark cell once, as ``bench/run.py`` does, and record per
``execute()`` call what the result line does not carry: the call's wall
time and each segment's ``compiles``, ``compile_s`` and ``phases_s``
(``windows[i]``, DESIGN.md §13).

    python3 scripts/bench_calls.py --out chiprun_out/a.json \\
        [--root <checkout>] [--cpu] -- \\
        --workload ycsb-a.1chip --seed 1234567891 --seconds 20 --trace 1

The result line is printed and the exit code returned as ``bench/run.py``
prints and returns them.  ``--root`` runs another checkout's ``bench/``
and ``src/`` (say, a parent commit with this tree's ``bench/`` laid over
it); ``--cpu`` runs a cell without a TPU.  ``--out`` gets one JSON object:

- ``fill_compiles``, ``setup_compiles``, ``setup_compile_s``: backend
  compiles (persistent-cache loads included) in the fill call, and in the
  fill and warm-up calls; ``window_compiles``: in the window's calls.
  None for a program whose ``windows`` do not count them.
- ``slow_calls``: the window's calls over 100 ms, as ``[ms, [[compiles,
  compile_s, phases_s] per segment]]``: where a stall fell.
- ``lat_ms_first32``, ``lat_ms_rest``, ``n_calls``: mean latency of the
  window's first 32 calls (those a ``--trace 1`` run profiles) and of the
  rest.  The cost of tracing is a traced run's first-32 mean against an
  untraced run's on the same seed.
- traced runs: ``bench_execute_ms`` (mean ``bench.execute`` span),
  ``ditto_execute_ms`` and ``phases_ms_per_call`` (the three phase spans,
  per call), so the phases' tiling of the call can be checked; and
  ``phase_detail``, the host events inside each phase and the fullest
  chip's busy time there (None without a device plane).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
FIRST = 32     # calls a --trace 1 run profiles (the mixes' trace_calls)
SLOW_S = 0.1   # a call over this is a stall, as bench/loop.py logs it


def _mean_ms(spans, n) -> float | None:
    return sum(e - b for _, b, e in spans) / n / 1e6 if n else None


def _traced(s) -> dict:
    """The phase spans of a traced window against its calls."""
    calls = [x for x in s.spans if x[0] == "bench.execute"]
    rec = {"bench_execute_ms": _mean_ms(calls, len(calls))}
    whole = [x for x in s.host if x[0] == "ditto.execute"]
    rec["ditto_execute_ms"] = _mean_ms(whole, len(whole))
    spans = {p: [x for x in s.host if x[0] == "ditto.execute." + p]
             for p in ("launch", "wait", "fetch")}
    rec["phases_ms_per_call"] = {p: _mean_ms(v, len(calls))
                                 for p, v in spans.items()}
    t0, t1 = s.window()
    dev = max(s.devices, key=lambda d: s.busy(d, t0, t1), default=None)
    detail = {}
    for p, within in spans.items():
        n, tot = max(len(within), 1), {}
        for name, b, e in s.host:
            if name.startswith(("ditto.", "bench.")):
                continue
            if any(sb <= b and e <= se for _, sb, se in within):
                tot[name] = tot.get(name, 0) + (e - b)
        detail[p] = {
            "span_ms_per_call": _mean_ms(within, n),
            "busy_ms_per_call": None if dev is None else sum(
                s.busy(dev, b, e) for _, b, e in within) / n / 1e6,
            "host_events_ms_per_call": sorted(
                ((k[:90], v / n / 1e6) for k, v in tot.items()),
                key=lambda x: -x[1])[:25]}
    rec["phase_detail"] = detail
    return rec


def _total(calls, i):
    v = [w[i] for _, ws in calls for w in ws]
    return None if any(x is None for x in v) else sum(v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True, type=Path)
    ap.add_argument("--root", type=Path, default=REPO)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("run", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    root = args.root.resolve()
    for p in (str(root), str(root / "src")):
        if p in sys.path:
            sys.path.remove(p)
        sys.path.insert(0, p)
    from bench import harness, run
    from repro.core import execute as program

    run.T_START = T0
    box, calls = {}, []
    system_of = harness.system

    def system(name, root=harness.ROOT):
        mod = system_of(name, root)
        run_cell = mod.run

        def wrapped(job, t_start, program=None):
            box["out"] = run_cell(job, t_start, program=program)
            return box["out"]

        mod.run = wrapped
        return mod

    def timed(*a, **k):
        t = time.perf_counter()
        res = program(*a, **k)
        calls.append((time.perf_counter() - t,
                      [[w.get("compiles"), w.get("compile_s"),
                        w.get("phases_s")] for w in res.windows]))
        return res

    harness.system = system
    try:
        rc = run.main([a for a in args.run if a != "--"], root=root,
                      require_tpu=not args.cpu, program=timed)
    finally:
        harness.system = system_of
    rec = {"rc": rc}
    out = box.get("out")
    if out is not None:
        warm = out.traffic["warmup_calls"]
        window = calls[1 + warm:]
        lat = out.latencies_s
        rec.update(
            n_calls=len(lat),
            lat_ms_first32=sum(lat[:FIRST]) / max(len(lat[:FIRST]), 1) * 1e3,
            lat_ms_rest=sum(lat[FIRST:]) / max(len(lat[FIRST:]), 1) * 1e3,
            fill_compiles=_total(calls[:1], 0),
            setup_compiles=_total(calls[:1 + warm], 0),
            setup_compile_s=_total(calls[:1 + warm], 1),
            window_compiles=_total(window, 0),
            slow_calls=[[t * 1e3, ws] for t, ws in window if t > SLOW_S])
        if out.traced is not None:
            rec.update(_traced(out.traced))
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(rec))
    return rc


if __name__ == "__main__":
    sys.exit(main())
