"""``scripts/bench_calls.py``: one tiny cell on the CPU, run as
``bench/run.py`` runs it, with each ``execute()`` call's compiles and
phases recorded beside the result line."""

import contextlib
import importlib.util
import io
import json

import pytest

import _perfbench_tiny
from _perfbench_tiny import REPO


def _script():
    spec = importlib.util.spec_from_file_location(
        "bench_calls", REPO / "scripts" / "bench_calls.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("trace", (0, 1))
def test_a_run_records_its_calls(tmp_path, trace):
    import jax

    from repro.core.execute import clear_jit_cache
    clear_jit_cache()  # so this run's fill compiles, whatever ran before
    jax.clear_caches()
    root = _perfbench_tiny.make_root(tmp_path)
    out = tmp_path / "calls.json"
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        rc = _script().main(
            ["--out", str(out), "--root", str(root), "--cpu", "--", "--workload", "tiny.a", "--seed", "3000000017",
             "--seconds", "0.5", "--trace", str(trace)])
    assert rc == 0
    line = json.loads(printed.getvalue().strip().splitlines()[-1])
    assert line["correct"]
    rec = json.loads(out.read_text())
    assert rec["rc"] == 0 and rec["n_calls"] >= 1
    # the fill and warm-up compile the runners; the window runs them
    assert rec["fill_compiles"] >= 1
    assert rec["setup_compiles"] >= rec["fill_compiles"]
    assert rec["setup_compile_s"] > 0
    assert rec["window_compiles"] == 0
    assert rec["lat_ms_first32"] > 0
    for ms, segments in rec["slow_calls"]:
        assert ms > 100 and all(set(p) == {"launch", "wait", "fetch"}
                                for _, _, p in segments)
    if not trace:
        assert "bench_execute_ms" not in rec
        return
    phases = rec["phases_ms_per_call"]
    assert set(phases) == {"launch", "wait", "fetch"}
    assert 0 < sum(phases.values()) <= rec["ditto_execute_ms"]
    assert rec["ditto_execute_ms"] <= rec["bench_execute_ms"]
    assert set(rec["phase_detail"]) == set(phases)
