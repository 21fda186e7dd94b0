"""The chip smoke's phases at a tiny size on the CPU, and its refusal to
report anything without a TPU.  Catches a smoke broken by a later change
before it costs chip time; says nothing about the chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY = dict(n_keys=20_000, n_buckets=256, capacity=1024, lanes=16,
            load_chunks=2, run_ops=4096, cap_slots=1024, cap_lanes=8,
            cap_ops=1024, four_rounds=8, leg_rounds=8)


def _env(**kw):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("XLA_FLAGS", None)
    env.update(kw)
    return env


def test_one_chip_phases_pass_at_tiny_size():
    out = chip_smoke.one_chip(TINY, on_tpu=False)
    assert 0.0 < out["ycsb_a_hit_rate"] < out["ycsb_c_hit_rate"] <= 1.0
    assert out["cpu_equal"]


def test_four_chip_phase_passes_on_four_cpu_devices():
    code = ("import json, chip_smoke\n"
            f"print('OUT', json.dumps(chip_smoke.four_chips({TINY!r})))\n")
    env = _env(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] += os.pathsep + str(REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("OUT ")]
    assert 0.0 <= json.loads(line[-1][4:])["cluster_hit_rate"] <= 1.0


def test_no_tpu_means_no_result():
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=_env(JAX_PLATFORMS="cpu"), cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
