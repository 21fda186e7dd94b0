"""The harness finds a cell, configuration, traffic mix and metric added
as files, and runs them at a tiny size on the CPU; without a TPU, or
without the program, the command prints no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import _perfbench_tiny as tiny

NEW_METRIC = ({"unit": "calls/s", "better": "higher", "source": "host_clock"},
              "def read(ctx):\n    return len(ctx.latencies_s) / ctx.window_s\n")
NEW_LAYER_METRIC = (
    {"unit": "calls", "better": "higher", "source": "device_trace",
     "layer": "facade (core/execute.py)", "moves": "ops_per_s",
     "workloads": ["tiny.a"]},
    "def read(ctx):\n"
    "    s = ctx.traced\n"
    "    return len([x for x in s.spans if x[0] == 'bench.execute'])\n")


def test_added_files_are_found_by_name_and_run(tmp_path):
    root = tiny.make_root(tmp_path, extra_metrics={
        "calls_per_s": NEW_METRIC, "traced_calls": NEW_LAYER_METRIC})
    res = tiny.run_cell(root)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["calls_per_s"]["value"] * 8 * 16 == \
        pytest.approx(m["ops_per_s"]["value"])
    assert {"ops_per_s", "op_p99_ms", "setup_s"} <= set(m)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"hits_gap", "slots_gap", "weights_gap",
                                  "counters_gap", "occupancy_gap",
                                  "records_gap", "issued_gap"}

    traced = tiny.run_cell(root, trace=1, seed=4)
    assert traced["correct"], traced["checks"]
    # the traced run reports per-layer metrics only; the CPU trace has no
    # device plane, so the device readers find nothing and stay silent
    assert traced["metrics"]["traced_calls"]["value"] == 4
    assert "ops_per_s" not in traced["metrics"]
    assert "cache.evictions_per_op" in traced["metrics"]
    assert 0 < traced["metrics"]["hit_rate"]["value"] < 1
    assert "access_round_roofline" not in traced["metrics"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)
    return env


def test_no_tpu_means_no_result():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ycsb-a.1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny.REPO, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout


def test_checkout_without_the_program_gives_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files:
    the harness passes its look for a chip here (as it would on one) and
    then cannot import the system under test."""
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    code = ("import sys; sys.path.insert(0, '.')\n"
            "from bench import run\n"
            "sys.exit(run.main(['--workload', 'ycsb-a.1chip', '--seed', '1',"
            " '--seconds', '1'], require_tpu=False))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=_env(), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout
    assert "repro" in out.stderr


def test_spec_names_only_files_that_exist():
    spec = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (tiny.REPO / c["file"]).is_file()
    for w in spec["workloads"]:
        assert (tiny.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (tiny.BENCH / "metrics" / f"{m['name']}.py").is_file()
