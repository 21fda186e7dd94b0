"""What the benchmark finds by name: cells, configurations, traffic mixes,
systems and metric readers.

``BENCHMARK.json`` at the checkout's root lists the cells.  A cell names a
configuration (its ``file`` is given in ``BENCHMARK.json``) and a traffic
mix (``bench/traffic/<name>.json``).  The configuration names the system
that drives it (``bench/systems/<system>.py``, which has ``run(job)``).
Every metric is read by ``bench/metrics/<name>.py``, which has
``read(ctx)`` returning a number, or None where it finds nothing to read.
Adding any of these is adding files and entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(Exception):
    """The checkout does not hold what the named cell needs."""


@dataclass
class Job:
    """One run of one cell, as the command line and the files give it."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    root: Path = ROOT
    scratch: Path = None


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError as e:
        raise SpecError(f"missing {path}") from e


def resolve(workload: str, root: Path = ROOT) -> tuple:
    """The spec, the cell, its configuration and its traffic mix."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"no cell {workload!r} in BENCHMARK.json "
                        f"(cells: {', '.join(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if cell["config"] not in configs:
        raise SpecError(f"cell {workload!r} names unknown configuration "
                        f"{cell['config']!r}")
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, config, traffic


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        "bench/peaks.json")
    return table[device_kind]


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing {path}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def system(name: str, root: Path = ROOT):
    return _load_module(root / "bench" / "systems" / f"{name}.py",
                        f"bench_system_{name}")


def metrics_of(spec: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics (untraced runs) or per-layer ones
    (traced runs): those whose ``workloads`` list names the cell, or that
    have no such list."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metrics(entries: list, ctx, root: Path = ROOT) -> dict:
    """Run each metric's reader; a reader that returns None is left out."""
    out = {}
    for m in entries:
        name = m["name"]
        reader = _load_module(root / "bench" / "metrics" / f"{name}.py",
                              "bench_metric_" + name.replace(".", "_")
                              .replace("-", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": m["unit"]}
    return out
