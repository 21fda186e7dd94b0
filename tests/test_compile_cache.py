"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, else to ``<repo>/.jax_cache`` — and nowhere else.  Each case runs
in a child: the cache directory is process-wide JAX state."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_CHILD = """
import jax
from repro.compile_cache import enable_compile_cache
print("DIR", enable_compile_cache(), jax.config.jax_compilation_cache_dir)
if jax.config.jax_compilation_cache_dir:
    jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(8)).block_until_ready()
"""


def _run(env_dir, code=_CHILD):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=str(REPO), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("DIR ")]
    return line[-1].split()[1:]


def _listing(path):
    return sorted(p.name for p in path.iterdir()) if path.exists() else []


@pytest.mark.fast
def test_env_dir_is_used_and_nothing_else(tmp_path):
    repo_cache = REPO / ".jax_cache"
    before = _listing(repo_cache)
    got, cfg_dir = _run(tmp_path / "cc")
    assert got == cfg_dir == str(tmp_path / "cc")
    assert _listing(tmp_path / "cc"), "no cache entry was written"
    assert _listing(repo_cache) == before


@pytest.mark.fast
def test_default_dir_is_fixed_under_the_repo():
    # No compile here: the point is where the cache would go.
    got, cfg_dir = _run(None, code=_CHILD.split("if jax.config")[0])
    assert got == cfg_dir == str(REPO / ".jax_cache")
