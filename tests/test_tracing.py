"""The program's own observability: the phase spans and compile counts of
every execute() call, and the named scopes of the access round's stages."""

import contextlib
import re

import jax
import numpy as np

from repro.core import CacheConfig, execute, make
from repro.core.execute import clear_jit_cache, runner_hlo

PHASES = ("launch", "wait", "fetch")
STAGES = ("probe", "hit_update", "evict", "apply", "account")
LANES, ROUNDS = 16, 8


def _cfg(n_buckets=64):
    return CacheConfig(n_buckets=n_buckets, assoc=8, capacity=4 * n_buckets,
                       experts=("lru", "lfu"), value_words=4)


def _keys(seed=0):
    return np.random.default_rng(seed).integers(
        1, 2000, (ROUNDS, LANES)).astype(np.uint32)


def _host_spans(path, prefix):
    import glob
    f = sorted(glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True))
    data = jax.profiler.ProfileData.from_file(f[-1])
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def test_call_span_holds_its_phases_and_they_tile_it(tmp_path):
    # a pool large enough that the device's work, not the host's
    # bookkeeping around the segment loop, takes most of a call
    cache = make(_cfg(n_buckets=2**14), 4 * LANES, seed=1)
    keys = np.tile(_keys(), 4)
    cache = execute(cache, keys, plan=None).cache      # compile first
    with jax.profiler.trace(str(tmp_path)):
        res = execute(cache, keys, plan=None)
    spans = _host_spans(tmp_path, "ditto.execute")
    (call,) = [x for x in spans if x[0] == "ditto.execute"]
    kids = {x[0].rsplit(".", 1)[1]: x for x in spans if x is not call}
    assert sorted(kids) == sorted(PHASES)
    order = [kids[p] for p in PHASES]
    assert call[1] <= order[0][1] and order[-1][2] <= call[2]
    assert all(a[2] <= b[1] for a, b in zip(order, order[1:]))  # in turn
    tiled = sum(e - b for _, b, e in order)
    assert tiled >= 0.95 * (call[2] - call[1])
    phases = res.windows[0]["phases_s"]
    assert sorted(phases) == sorted(PHASES)
    assert all(v > 0 for v in phases.values())


def test_compiles_are_counted_per_call():
    clear_jit_cache()
    cache = make(_cfg(n_buckets=96), LANES, seed=2)
    first = execute(cache, _keys(1), plan=None)
    w = first.windows[0]
    assert w["compiles"] >= 1 and w["compile_s"] > 0
    again = execute(first.cache, _keys(2), plan=None)
    assert again.windows[0]["compiles"] == 0
    assert again.windows[0]["compile_s"] == 0.0
    assert sorted(again.windows[0]["phases_s"]) == sorted(PHASES)


def test_a_compiling_call_teaches_the_planner_nothing():
    from repro.workloads.plan import PlanCostModel
    clear_jit_cache()
    model = PlanCostModel()
    seen = []
    model.observe = lambda *a, **k: seen.append(a)
    cache = make(_cfg(n_buckets=112), LANES, seed=3)
    res = execute(cache, _keys(3), plan=None, model=model)
    assert res.windows[0]["compiles"] >= 1 and not seen
    execute(res.cache, _keys(4), plan=None, model=model)
    assert len(seen) == 1


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def test_the_runner_carries_every_stage_scope():
    names = _op_names(runner_hlo(_cfg(), LANES, ROUNDS))
    for st in STAGES:
        assert any(f"ditto.{st}" in n.split("/") for n in names), st


def _stripped(text):
    """The module's computations without op metadata or the tables of
    source locations (what the scopes are allowed to change)."""
    return "\n".join(
        re.sub(r", metadata=\{[^}]*\}", "", line)
        for line in text.splitlines()
        if not re.match(r"(\d|FileNames|FunctionNames|FileLocations"
                        r"|StackFrames)", line))


def test_the_scopes_change_no_operation(monkeypatch):
    cfg = _cfg()
    clear_jit_cache()
    scoped = runner_hlo(cfg, LANES, ROUNDS)
    clear_jit_cache()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = runner_hlo(cfg, LANES, ROUNDS)
    clear_jit_cache()
    assert "ditto." in scoped and "ditto." not in plain
    assert _stripped(scoped) == _stripped(plain)


def test_runner_hlo_is_the_program_execute_runs():
    """Lowered from shapes alone, it is the program a steady-state call
    compiles from its real arguments."""
    import jax.numpy as jnp

    from repro.core.execute import _runner
    from repro.core.types import merge_exec_config
    cfg, seed = _cfg(), 5
    res = execute(make(cfg, LANES, seed=seed), _keys(seed), plan=None)
    xc = cfg.split()[1]
    fn = _runner(merge_exec_config(cfg, xc), False,
                 jax.default_backend() != "cpu", xc.interpret)
    k = jnp.asarray(_keys(seed + 1))
    real = fn.lower(res.state, res.clients, res.stats, k,
                    jnp.zeros(k.shape, bool), jnp.ones(k.shape, jnp.uint32),
                    jnp.zeros(k.shape, jnp.uint32)).compile().as_text()
    assert runner_hlo(cfg, LANES, ROUNDS) == real
