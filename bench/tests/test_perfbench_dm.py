"""The four-node cell at a tiny size on four CPU devices: a sound run is
``correct``, each fault planted in the timed path fails the gap that
measures it, the control fails; and the router's readers on traces and
counters built by hand."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import _perfbench_tiny  # noqa: F401  (puts the repo on sys.path)
from _perfbench_tiny import BENCH, REPO, TINY_MIX
from bench import router_stages
from bench.loop import Outcome
from bench.tracing import Device, Summary

CELL = "tiny.a4"


def make_root(tmp) -> str:
    """A copy of ``bench/`` whose BENCHMARK.json holds one tiny cell of the
    four-node system (configuration ``tiny4``, mix ``tiny-ycsb-a``)."""
    import shutil
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "ditto-4chip.json").read_text())
    cfg.update(name="tiny4", lanes=16, fill_lanes=64,
               fill_rounds_per_call=8, fill_route_factor=2,
               pool=dict(cfg["pool"], n_buckets=256, capacity=1024))
    (root / "bench" / "configs" / "tiny4.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "ycsb-a.json").read_text())
    # hit_rate over fewer operations: a loaded host still reaches them
    mix.update(TINY_MIX, hit_rate_ops_per_node=500,
               max_ops_per_s_per_node=1_000_000)
    (root / "bench" / "traffic" / "tiny-ycsb-a.json").write_text(
        json.dumps(mix))
    spec["configs"].append(dict(spec["configs"][-1], name="tiny4",
                                file="bench/configs/tiny4.json"))
    spec["workloads"].append(dict(name=CELL, config="tiny4",
                                  traffic="tiny-ycsb-a", chips=4,
                                  why="tiny"))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


# Runs in a child with four host devices: each case prints one line,
# "CASE <name> <checks as JSON>".
_CHILD = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import contextlib, io, json
from pathlib import Path
import jax, jax.numpy as jnp, numpy as np
root = Path(sys.argv[1])
from bench import control, run
from repro.core import execute


def serving(keys):
    return keys.shape[1] == 16


def wrong_owner(cl, keys, **kw):
    if not serving(keys):
        return execute(cl, keys, **kw)
    r = cl.routes
    every7 = jnp.arange(r.primary.shape[0]) % 7 == 0
    moved = jax.device_put(jnp.where(every7, (r.primary + 1) % cl.n_shards,
                                     r.primary), r.primary.sharding)
    res = execute(cl._replace(routes=r._replace(primary=moved)), keys, **kw)
    return res._replace(cache=res.cache._replace(routes=r))


def dropped_fc_delta(cl, keys, **kw):
    res = execute(cl, keys, **kw)
    if not serving(keys):
        return res
    c = res.cache
    fd = c.dm.clients.fc_delta
    # shard 0's lanes of sources 1-3 lose what they hold
    fd = jax.device_put(fd.at[4:16].set(0), fd.sharding)
    dm = c.dm._replace(clients=c.dm.clients._replace(fc_delta=fd))
    return res._replace(cache=c._replace(dm=dm))


once = []


def shard_scalar(cl, keys, **kw):
    res = execute(cl, keys, **kw)
    if not serving(keys) or once:
        return res
    once.append(1)
    c = res.cache
    n = c.dm.state.n_cached
    n = jax.device_put(n.at[2].add(1), n.sharding)
    dm = c.dm._replace(state=c.dm.state._replace(n_cached=n))
    return res._replace(cache=c._replace(dm=dm))


for name, program in (("sound", None), ("wrong_owner", wrong_owner),
                      ("dropped_fc_delta", dropped_fc_delta),
                      ("shard_scalar", shard_scalar)):
    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = run.main(["--workload", "tiny.a4", "--seed", "4294967311",
                       "--seconds", "1", "--trace", "0"],
                      require_tpu=False, program=program, root=root)
    assert rc == 0, out.getvalue()[-2000:]
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    print("CASE", name, json.dumps(res["checks"]), flush=True)
checks = control.readings("tiny.a4", 7, rows=600, root=root)
print("CASE control", json.dumps(checks), flush=True)
"""


@pytest.fixture(scope="module")
def cases(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("dm"))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(REPO / "src"), str(REPO)]))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _CHILD, root],
                         capture_output=True, text=True, env=env,
                         cwd=str(REPO), timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    got = {}
    for line in out.stdout.splitlines():
        if line.startswith("CASE "):
            _, name, checks = line.split(" ", 2)
            got[name] = json.loads(checks)
    return got


def _failing(checks):
    return [k for k, c in checks.items() if c["value"] > c["limit"]]


def test_a_sound_run_is_correct(cases):
    assert _failing(cases["sound"]) == [], cases["sound"]


@pytest.mark.parametrize("fault,gap", [("wrong_owner", "slots_gap"),
                                       ("dropped_fc_delta", "slots_gap"),
                                       ("shard_scalar", "occupancy_gap")])
def test_a_planted_fault_fails_its_gap(cases, fault, gap):
    assert gap in _failing(cases[fault]), cases[fault]


def test_a_shard_scalar_off_by_one_reads_one(cases):
    assert _failing(cases["shard_scalar"]) == ["occupancy_gap"]
    assert cases["shard_scalar"]["occupancy_gap"]["value"] == 1


def test_the_control_is_not_correct(cases):
    assert _failing(cases["control"]), cases["control"]


# ---------------------------------------------------------------------
# The router's readers on a trace built by hand
# ---------------------------------------------------------------------

GROUP_OF = {"route": "ditto.route", "exchange": "ditto.exchange",
            "sync": "ditto.sync", "probe": "ditto.probe",
            "hit": "ditto.hit_update", "evict": "ditto.evict",
            "apply": "ditto.apply", "account": "ditto.account"}
# (instruction, start offset, duration) of one traced round's operations
ROUND = [("route", 10, 20), ("exchange", 30, 15), ("probe", 45, 10),
         ("hit", 55, 10), ("evict", 65, 20), ("apply", 85, 20),
         ("account", 105, 5), ("exchange", 100, 10), ("sync", 115, 5)]
HLO = "\n".join(
    ["HloModule jit_cluster_run, is_scheduled=true"]
    + [f'  %fusion.{i} = u32[8]{{0}} fusion(%p), kind=kLoop, '
       f'metadata={{op_name="jit(cluster_run)/while/body/'
       f'{GROUP_OF[g]}/x"}}' for i, (g, _, _) in enumerate(ROUND)]
    + ['  %copy.99 = u32[8]{0} copy(%p)'])


def _summary():
    ops, mods = [], []
    for t in (1000, 2000):
        mods.append(("jit_cluster_run(4)", t, 150))
        ops += [(f"%fusion.{i} = u32[8]{{0}} fusion(u32[8]{{0}} %p), "
                 "kind=kLoop", t + b, d) for i, (_, b, d) in enumerate(ROUND)]
        ops.append(("%copy.99 = u32[8]{0} copy(u32[8]{0} %p)", t + 130, 10))
    spans = [("bench.execute", 900, 1400), ("bench.execute", 1900, 2400)]
    return Summary(spans=spans, devices=[Device("/device:TPU:0", ops=ops,
                                                modules=mods)], host=spans)


def _ctx(summary, rounds=2):
    cfg = json.loads((BENCH / "configs" / "ditto-4chip.json").read_text())
    return Outcome(setup_s=1.0, window_s=1.0, latencies_s=[0.5, 0.5],
                   requests_per_call=8, hit_rate=0.5, peak_bytes=1,
                   counters={"gets": 8, "sets": 8, "evictions": 1},
                   attempted=16, failed=0, config=cfg,
                   traffic={"rounds_per_call": 1}, traced=summary,
                   traced_rounds=rounds)


def _reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "t_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def hlo(monkeypatch):
    monkeypatch.setattr(router_stages, "runner_text", lambda ctx: HLO)
    router_stages._LAST[:] = [None, None]
    yield
    router_stages._LAST[:] = [None, None]


@pytest.mark.parametrize("metric,ns", [
    ("router.route_us", 20), ("router.exchange_us", 25),
    ("router.sync_us", 5),
    # probe..account: [45, 110) without a gap
    ("router.shard_access_us", 65)])
def test_a_router_reader_reads_its_scope_per_round(hlo, metric, ns):
    """Two traced calls of one round each: per round, each group is the
    union of its operations' intervals (the two exchanges do not meet,
    the access stages tile one interval)."""
    assert _reader(metric)(_ctx(_summary())) == pytest.approx(ns / 1e3)


def test_the_router_readers_stay_within_the_round(hlo):
    ctx = _ctx(_summary())
    parts = sum(_reader(m)(ctx) for m in (
        "router.route_us", "router.exchange_us", "router.sync_us",
        "router.shard_access_us"))
    assert parts <= _reader("access_round.device_us")(ctx) == 0.15


@pytest.mark.parametrize("scope", ["ditto.route", "ditto.exchange",
                                   "ditto.sync"])
def test_a_runner_missing_a_router_scope_is_an_error(monkeypatch, hlo,
                                                     scope):
    renamed = HLO.replace(f"{scope}/", f"{scope}_v2/")
    monkeypatch.setattr(router_stages, "runner_text", lambda ctx: renamed)
    with pytest.raises(LookupError, match=scope.split(".")[1]):
        _reader("router.route_us")(_ctx(_summary()))


def test_an_hlo_of_another_program_is_an_error(monkeypatch, hlo):
    other = HLO.replace("%fusion.3 = u32[8]{0}", "%fusion.3 = u32[16]{0}")
    monkeypatch.setattr(router_stages, "runner_text", lambda ctx: other)
    with pytest.raises(LookupError, match="fusion.3"):
        _reader("router.sync_us")(_ctx(_summary()))


def test_a_program_without_a_cluster_runner_reads_nothing(monkeypatch):
    """The parent of the cluster cell: no ``cluster_runner_hlo``, so every
    router reader gives None and none raises."""
    import importlib
    ex = importlib.import_module("repro.core.execute")
    monkeypatch.delattr(ex, "cluster_runner_hlo")
    router_stages._LAST[:] = [None, None]
    ctx = _ctx(_summary())
    for m in ("router.route_us", "router.exchange_us", "router.sync_us",
              "router.shard_access_us", "router.load_imbalance"):
        assert _reader(m)(ctx) is None, m
    router_stages._LAST[:] = [None, None]


def test_load_imbalance_from_per_shard_counters():
    """The busiest shard's gets + sets over the mean of the four."""
    from types import SimpleNamespace

    from bench import harness
    dm = harness.system("dm")
    stats = SimpleNamespace(gets=np.array([10, 20, 30, 40]),
                            sets=np.array([10, 0, 10, 0]))
    assert dm.load_imbalance(stats) == pytest.approx(40 / 30)
    ctx = _ctx(None)
    assert _reader("router.load_imbalance")(ctx) is None
    ctx.load_imbalance = 1.25
    assert _reader("router.load_imbalance")(ctx) == 1.25


def test_the_benchmark_lists_the_router_metrics():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in spec["per_layer"]}
    cells = {c["name"]: c for c in spec["workloads"]}
    assert cells["ycsb-a.4chip"]["chips"] == 4
    for name in ("router.route_us", "router.exchange_us", "router.sync_us",
                 "router.shard_access_us", "router.load_imbalance"):
        assert (BENCH / "metrics" / f"{name}.py").is_file()
        assert entries[name]["workloads"] == ["ycsb-a.4chip"]
        assert entries[name]["moves"] == "ops_per_s"
