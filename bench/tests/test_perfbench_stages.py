"""The readers of the program's own spans and scopes (``bench/stages.py``):
on device and host events built by hand, and on a runner compiled on the
CPU."""

import importlib
import importlib.util
import json

import pytest

import _perfbench_tiny  # noqa: F401
from _perfbench_tiny import BENCH, REPO
from bench import stages
from bench.loop import Outcome
from bench.tracing import Device, Summary

STAGES = ("probe", "hit_update", "evict", "apply", "account")
PHASE_METRICS = ("execute.launch_ms_per_call", "execute.fetch_ms_per_call",
                 "execute.wait_idle_ms_per_call")
STAGE_METRICS = tuple(f"access_round.{s}_us" for s in STAGES) + (
    "access_round.outside_stages_us",)

# The runner's HLO, as the program compiles it: name = type opcode(...),
# each with the op_name of the scope it was traced under.
HLO = "\n".join([
    "HloModule jit_run, is_scheduled=true",
    "ENTRY %main.1 (p: u32[8]) -> u32[8] {",
    '  %while.5 = (s32[], /*index=1*/u32[8]{0}) while(%tuple.1), '
    'body=%body.1, metadata={op_name="jit(run)/while"}',
    '  %fusion.1 = u32[8]{0} fusion(%p), kind=kLoop, '
    'metadata={op_name="jit(run)/while/body/ditto.probe/gather"}',
    '  %fusion.2 = u32[8]{0} fusion(%fusion.1), kind=kLoop, '
    'metadata={op_name="jit(run)/while/body/ditto.hit_update/max"}',
    '  %fusion.3 = u32[8]{0} fusion(%fusion.2), kind=kLoop, '
    'metadata={op_name="jit(run)/while/body/ditto.evict/argmin"}',
    '  %fusion.4 = u32[8]{0} fusion(%fusion.3), kind=kCustom, '
    'metadata={op_name="jit(run)/while/body/ditto.apply/scatter"}',
    '  %reduce.6 = s32[] reduce(%fusion.4), dimensions={0}, '
    'metadata={op_name="jit(run)/while/body/ditto.account/reduce_sum"}',
    "  ROOT %copy.7 = u32[8]{0} copy(%fusion.4)",
    "}",
])


def _op(name, start, dur):
    rest = {"while.5": "(s32[], u32[8]{0}) while((s32[], u32[8]{0}) %t)",
            "reduce.6": "s32[] reduce(u32[8]{0} %fusion.4)",
            "copy.7": "u32[8]{0} copy(u32[8]{0} %fusion.4)"}.get(
                name, "u32[8]{0} fusion(u32[8]{0} %p), kind=kLoop")
    return (f"%{name} = {rest}", start, dur)


def _round(t):
    """One traced call at ``t``: a runner module of 200 ns whose loop holds
    one operation of each stage, then an operation of no stage."""
    return [_op("while.5", t + 10, 170), _op("fusion.1", t + 10, 30),
            _op("fusion.2", t + 40, 20), _op("fusion.3", t + 60, 40),
            _op("fusion.4", t + 100, 50), _op("reduce.6", t + 150, 20),
            _op("copy.7", t + 185, 10)]


def _summary(scoped=True, other=True):
    """Two traced calls, each with its phase spans and one runner module;
    a module of another program runs between them."""
    ops = _round(1000) + _round(2000)
    mods = [("jit_run(7)", 1000, 200), ("jit_run(7)", 2000, 200)]
    if other:
        ops.append(("%fusion.9 = f32[4]{0} fusion(f32[4]{0} %q)", 1500, 50))
        mods.append(("jit_other(3)", 1500, 50))
    spans = [("bench.execute", 900, 1400), ("bench.execute", 1900, 2400),
             ("bench.slice", 1450, 1460)]
    host = list(spans)
    if scoped:
        for b in (900, 1900):
            host += [("ditto.execute", b + 10, b + 490),
                     ("ditto.execute.launch", b + 20, b + 100),
                     ("ditto.execute.wait", b + 100, b + 320),
                     ("ditto.execute.fetch", b + 320, b + 480)]
    return Summary(spans=spans, devices=[Device("/device:TPU:0", ops=ops,
                                                modules=mods)], host=host)


def _ctx(summary, rounds=2):
    cfg = json.loads((BENCH / "configs" / "ditto-1chip.json").read_text())
    return Outcome(setup_s=1.0, window_s=1.0, latencies_s=[0.5, 0.5],
                   requests_per_call=8, hit_rate=0.5, peak_bytes=1,
                   counters={"gets": 8, "sets": 8, "evictions": 1},
                   attempted=16, failed=0, config=cfg,
                   traffic={"rounds_per_call": 1}, traced=summary,
                   traced_counters={"gets": 8, "sets": 8, "hits": 10,
                                    "misses": 6, "insert_drops": 1,
                                    "evictions": 1},
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
    monkeypatch.setattr(stages, "runner_text", lambda ctx: HLO)
    stages._LAST[:] = [None, None]
    yield
    stages._LAST[:] = [None, None]


def test_the_phase_readers():
    ctx = _ctx(_summary())
    assert _reader("execute.launch_ms_per_call")(ctx) == 80 / 1e6
    assert _reader("execute.fetch_ms_per_call")(ctx) == 160 / 1e6
    # the wait spans [1000, 1220] and [2000, 2220] hold 170 + 10 ns of
    # device work each (1000..1170 in the loop, then the copy)
    assert _reader("execute.wait_idle_ms_per_call")(ctx) == pytest.approx(
        (220 - 180) / 1e6)


def test_the_stage_readers_split_the_round(hlo):
    ctx = _ctx(_summary())
    got = {m: _reader(m)(ctx) for m in STAGE_METRICS}
    # per round: 30 + 20 + 40 + 50 + 20 ns of stages; the runner's 200 ns
    # less those is the loop's own time and the copy
    assert got == {"access_round.probe_us": 0.03,
                   "access_round.hit_update_us": 0.02,
                   "access_round.evict_us": 0.04,
                   "access_round.apply_us": 0.05,
                   "access_round.account_us": 0.02,
                   "access_round.outside_stages_us": 0.04}
    whole = _reader("access_round.device_us")(ctx)
    assert sum(got.values()) == pytest.approx(whole, rel=1e-12)


def test_a_runner_without_stage_scopes_is_an_error(monkeypatch, hlo):
    bare = HLO.replace("ditto.", "step.")
    monkeypatch.setattr(stages, "runner_text", lambda ctx: bare)
    with pytest.raises(LookupError, match="ditto.probe"):
        _reader("access_round.probe_us")(_ctx(_summary()))


@pytest.mark.parametrize("stage", STAGES)
def test_a_runner_missing_one_stage_scope_is_an_error(monkeypatch, hlo,
                                                      stage):
    """One renamed scope would read 0 and move its time to
    ``outside_stages_us``: every stage reader fails instead."""
    renamed = HLO.replace(f"ditto.{stage}/", f"ditto.{stage}_v2/")
    monkeypatch.setattr(stages, "runner_text", lambda ctx: renamed)
    for st in STAGES:
        stages._LAST[:] = [None, None]
        with pytest.raises(LookupError, match=f"ditto.{stage} "):
            _reader(f"access_round.{st}_us")(_ctx(_summary()))


@pytest.mark.parametrize("phase", ("launch", "wait", "fetch"))
def test_a_call_missing_one_phase_span_is_an_error(phase):
    """A renamed phase span, while ``ditto.execute`` stays, fails its
    reader instead of dropping the metric."""
    s = _summary()
    s.host[:] = [(n.replace(f".{phase}", f".{phase}_v2"), b, e)
                 for n, b, e in s.host]
    metric = {"launch": "execute.launch_ms_per_call",
              "wait": "execute.wait_idle_ms_per_call",
              "fetch": "execute.fetch_ms_per_call"}[phase]
    with pytest.raises(LookupError, match=f"ditto.execute.{phase} span"):
        _reader(metric)(_ctx(s))
    others = [m for m in PHASE_METRICS if m != metric]
    assert all(_reader(m)(_ctx(s)) is not None for m in others)


def test_an_hlo_of_another_program_is_an_error(monkeypatch, hlo):
    other = HLO.replace("%fusion.3 = u32[8]{0}", "%fusion.3 = u32[16]{0}")
    monkeypatch.setattr(stages, "runner_text", lambda ctx: other)
    with pytest.raises(LookupError, match="fusion.3"):
        _reader("access_round.evict_us")(_ctx(_summary()))


def test_a_program_without_spans_or_scopes_reads_nothing(monkeypatch):
    """A program from before the spans and scopes: every new reader gives
    None, and none raises."""
    ex = importlib.import_module("repro.core.execute")
    monkeypatch.delattr(ex, "runner_hlo")
    stages._LAST[:] = [None, None]
    ctx = _ctx(_summary(scoped=False))
    for m in PHASE_METRICS + STAGE_METRICS:
        assert _reader(m)(ctx) is None, m
    # spans but no way to name the stages: loud
    stages._LAST[:] = [None, None]
    with pytest.raises(LookupError, match="runner_hlo"):
        _reader("access_round.apply_us")(_ctx(_summary()))
    stages._LAST[:] = [None, None]


def test_existing_readers_read_the_same_with_or_without_the_new_spans():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    new = set(PHASE_METRICS + STAGE_METRICS)
    old = [m["name"] for m in spec["per_layer"] if m["name"] not in new]
    peaks = {"hbm_bytes_per_s": 819e9}
    for name in old:
        read = _reader(name)
        a, b = _ctx(_summary(scoped=False)), _ctx(_summary(scoped=True))
        a.peaks = b.peaks = peaks
        assert read(a) == read(b), name


def test_the_benchmark_lists_each_new_metric_with_its_reader():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in spec["per_layer"]}
    layers = {m["layer"] for m in entries.values()
              if m["name"] not in PHASE_METRICS + STAGE_METRICS}
    for name in PHASE_METRICS + STAGE_METRICS:
        m = entries[name]
        assert (BENCH / "metrics" / f"{name}.py").is_file()
        assert m["layer"] in layers and m["source"] == "device_trace"
        assert m["workloads"] == ["ycsb-a.1chip", "ycsb-c-fits.1chip"]


def test_stage_names_read_from_a_compiled_runner():
    """``op_stages`` of the program's own runner, compiled on the CPU: every
    stage has instructions, and a traced instruction finds its entry."""
    from repro.core import CacheConfig
    from repro.core.execute import runner_hlo
    cfg = CacheConfig(n_buckets=64, assoc=8, capacity=256,
                      experts=("lru", "lfu"), value_words=4)
    table = stages.op_stages(runner_hlo(cfg, 16, 8))
    found = {v[2] for v in table.values()}
    assert set(STAGES) <= found and None in found
    ty, opcode, _ = next(v for v in table.values() if v[2] == "apply")
    assert ty and opcode
