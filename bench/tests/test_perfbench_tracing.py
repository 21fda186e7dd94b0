"""The reduction from a profiler trace to numbers: on a trace recorded on
the CPU, and on device events built by hand."""

import jax
import jax.numpy as jnp
import pytest

import _perfbench_tiny  # noqa: F401
from bench import tracing
from bench.tracing import Device, Summary


def _summary():
    dev = Device("/device:TPU:0",
                 ops=[("fusion.1", 100, 50), ("fusion.2", 140, 30),
                      ("all-to-all.3", 300, 100), ("fusion.1", 500, 20)],
                 modules=[("jit_run(1)", 100, 80), ("jit_other(2)", 300, 220)])
    spans = [("bench.execute", 90, 450), ("bench.record", 450, 480),
             ("bench.execute", 480, 600), ("bench.slice", 200, 260)]
    host = spans + [("Execute", 100, 440), ("Allocate", 420, 460)]
    return Summary(spans=spans, devices=[dev], host=host), dev


def test_merge_unions_and_clips():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 10)]
    assert tracing.merge(ev, 0, 100) == [(0, 15), (30, 40)]
    assert tracing.merge(ev, 8, 35) == [(8, 15), (30, 35)]


def test_busy_window_and_module_time():
    s, dev = _summary()
    assert s.window() == (90, 600)
    assert s.busy(dev, 90, 600) == 70 + 100 + 20
    assert s.module_ns(dev, lambda n: n.startswith("jit_run"), 90, 600) == 80
    assert s.op_ns(dev, lambda n: "all-to-all" in n, 0, 1000) == 100
    assert s.top_ops(dev)[0] == ("all-to-all.3", 100)


def test_runner_time_and_a_missing_runner_is_an_error():
    s, _ = _summary()
    assert s.runner_ns(["jit_run"]) == 80
    with pytest.raises(LookupError, match="jit_renamed"):
        s.runner_ns(["jit_renamed"])
    # a trace without device modules has nothing to read: no error
    bare = Summary(spans=s.spans, devices=[Device("/device:TPU:0",
                                                  ops=[("f", 100, 5)])])
    assert bare.runner_ns(["jit_run"]) == 0


def test_idle_gaps_are_named_by_the_innermost_span():
    s, dev = _summary()
    gaps = s.idle_gaps(dev, 90, 600)
    assert gaps[0] == ("bench.slice > Execute", 130)  # 170..300, middle 235
    assert ("bench.record > Allocate", 100) in gaps   # 400..500, middle 450
    assert ("bench.execute", 80) in gaps              # 520..600
    assert sum(g for _, g in gaps) == 510 - 190


def test_reads_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    t = tracing.Tracer(tmp_path / "trace")
    t.start()
    for _ in range(3):
        with tracing.span("bench.execute"):
            f(x).block_until_ready()
    t.stop()
    s = t.summary()
    calls = [x for x in s.spans if x[0] == "bench.execute"]
    assert len(calls) == 3 and all(b < e for _, b, e in calls)
    w0, w1 = s.window()
    assert w0 == calls[0][1] and w1 == calls[-1][2]
    assert not (tmp_path / "trace").exists()


def test_short_names_keep_the_hlo_name_and_shape():
    assert tracing.short_name(
        "%fusion.505 = u32[8388608]{0:T(1024)} fusion(u32[8388608]{0:T(1024)}"
        " %fusion.502), kind=kCustom") == "%fusion.505 u32[8388608]"
    assert tracing.short_name(
        "%while.5 = (s32[]{:T(128)}, u32[8]) while(...)") == "%while.5"
