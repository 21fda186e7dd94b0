"""A checkout of the benchmark at a tiny size, for tests on the CPU."""

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_POOL = dict(n_buckets=256, capacity=1024)
TINY_MIX = dict(keys_per_node=2400, max_ops_per_s_per_node=2_000_000,
                hit_rate_ops_per_node=2000, warmup_calls=2, trace_calls=4)


def make_root(tmp: Path, traffic: str = "ycsb-a", cell: str = "tiny.a",
              extra_metrics: dict = None) -> Path:
    """A copy of ``bench/`` under ``tmp`` whose BENCHMARK.json holds one
    tiny cell (configuration ``tiny``, mix ``tiny-<traffic>``) added as
    new files, like a later PR would add them."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "ditto-1chip.json").read_text())
    cfg.update(name="tiny", lanes=16, fill_lanes=64,
               pool=dict(cfg["pool"], **TINY_POOL))
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    mix.update(TINY_MIX)
    if mix["fill"].get("every_key"):
        mix["keys_per_node"] = 400
    (root / "bench" / "traffic" / f"tiny-{traffic}.json").write_text(
        json.dumps(mix))
    spec["configs"].append(dict(spec["configs"][0], name="tiny",
                                file="bench/configs/tiny.json"))
    spec["workloads"].append(dict(name=cell, config="tiny",
                                  traffic=f"tiny-{traffic}", chips=1,
                                  why="tiny"))
    for name, (entry, code) in (extra_metrics or {}).items():
        (root / "bench" / "metrics" / f"{name}.py").write_text(code)
        group = "per_layer" if "layer" in entry else "end_to_end"
        spec[group].append(dict(entry, name=name))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_cell(root: Path, cell: str = "tiny.a", seed: int = 3, trace: int = 0,
             program=None, seconds: float = 0.5) -> dict:
    """One run of the cell on the CPU; the result line as a dict."""
    import contextlib
    import io

    from bench import run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)],
                      require_tpu=False, program=program, root=root)
    assert rc == 0, out.getvalue()[-2000:]
    return json.loads(out.getvalue().strip().splitlines()[-1])
