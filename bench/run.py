"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload ycsb-a.1chip --seed 7 --seconds 20 --trace 0

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json`` (see ``bench/harness.py``).  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window's first calls.
Every run checks what the timed path produced against the plain reference
(``bench/reference.py``) and prints each number compared beside its limit,
last on standard error and last in the result line.  The last line of
standard output is the result.  Without a TPU, or with fewer chips than
the cell asks for, the command prints no result and exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _paths() -> None:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def _compile_cache(where: Path) -> None:
    """JAX's persistent compilation cache at one fixed place in the
    checkout, so that only a cell's first run there compiles; the TPU
    runtime's own log files are not written."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(where)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(where))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result_line(spec: dict, job, out, devs, peaks) -> dict:
    """The result object: correctness, counts, metrics, device and, for a
    traced run, the breakdown; the numbers compared come last."""
    from bench import harness
    out.peaks = peaks
    metrics = harness.read_metrics(
        harness.metrics_of(spec, job.cell["name"], job.trace), out, job.root)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out.peak_bytes}
    res = {"correct": all(c["value"] <= c["limit"]
                          for c in out.checks.values()),
           "attempted": out.attempted, "failed": out.failed,
           "metrics": metrics, "device": device}
    if job.trace and out.traced is not None:
        s = out.traced
        win = s.window()
        if win and s.devices:
            t0, t1 = win
            busy = [s.busy(d, t0, t1) for d in s.devices]
            device["busy_s"] = sum(busy) / len(busy) / 1e9
            device["window_s"] = (t1 - t0) / 1e9
            top = s.devices[busy.index(max(busy))]
            res["breakdown"] = {
                "device_ops": [[n, v / 1e9] for n, v in s.top_ops(top)],
                "idle_gaps": [[n, v / 1e9]
                              for n, v in s.idle_gaps(top, t0, t1)]}
    res["checks"] = out.checks
    return res


def main(argv=None, require_tpu: bool = True, program=None,
         root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _paths()
    if require_tpu:
        _compile_cache(root / ".bench_cache" / "jax")
    from bench import harness
    try:
        spec, cell, config, mix = harness.resolve(args.workload, root)
        system = harness.system(config["system"], root)
    except (harness.SpecError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    peaks = None
    if require_tpu:
        if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
            print(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
                  f"chip(s); JAX found {len(devs)} {devs[0].platform} "
                  "device(s)", file=sys.stderr)
            return 3
        try:
            peaks = harness.peaks(devs[0].device_kind, root)
        except harness.SpecError as e:
            print(f"bench: {e}", file=sys.stderr)
            return 3
    job = harness.Job(cell=cell, config=config, traffic=mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      root=root,
                      scratch=root / ".bench_cache" / "trace" / cell["name"])
    from bench.loop import OutOfTrace
    try:
        out = system.run(job, T_START, program=program)
    except OutOfTrace as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    try:
        res = result_line(spec, job, out, devs, peaks)
    except LookupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
