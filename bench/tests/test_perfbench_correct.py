"""``correct`` comes out false for the control (the reference in bfloat16
weights in the program's place) and for each fault the cell can have,
planted in the timed path under a run that skips the look for a chip."""

import numpy as np
import pytest

import _perfbench_tiny as tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("correct"))


def test_control_is_not_correct(root):
    from bench import control
    for seed in (1, 2):
        checks = control.readings("tiny.a", seed, rows=600, root=root)
        assert any(c["value"] > c["limit"] for c in checks.values()), checks


def _state_unchanged(cache, keys, **kw):
    from repro.core import execute
    res = execute(cache, keys, **kw)
    return res._replace(cache=cache) if keys.shape[1] == 16 else res


def _half_batch(cache, keys, **kw):
    from repro.core import execute
    if keys.shape[1] == 16:
        keys = np.where(np.arange(16) < 8, keys, 0).astype(np.uint32)
    return execute(cache, keys, **kw)


def _answer_altered(cache, keys, **kw):
    from repro.core import execute
    res = execute(cache, keys, **kw)
    if keys.shape[1] == 16:
        hits = np.array(res.hits)
        hits[0] += 1
        res = res._replace(hits=hits)
    return res


def _records_unchanged(cache, keys, **kw):
    from repro.core import execute
    res = execute(cache, keys, **kw)
    if keys.shape[1] == 16:
        st = res.cache.state._replace(values=cache.state.values)
        res = res._replace(cache=res.cache._replace(state=st))
    return res


def _record_altered(cache, keys, **kw):
    from repro.core import execute
    res = execute(cache, keys, **kw)
    if keys.shape[1] == 16:
        st = res.cache.state
        st = st._replace(values=st.values.at[7, 3].add(1))
        res = res._replace(cache=res.cache._replace(state=st))
    return res


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered, _records_unchanged,
                                   _record_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered", "records_unchanged",
                              "record_altered"])
def test_fault_in_the_timed_path_is_not_correct(root, fault):
    res = tiny.run_cell(root, program=fault, seed=5)
    assert not res["correct"], res["checks"]
    if fault in (_records_unchanged, _record_altered):
        # the records alone betray these
        assert [k for k, c in res["checks"].items()
                if c["value"] > c["limit"]] == ["records_gap"]


def test_narrower_counters_show_in_the_table(tmp_path, monkeypatch):
    """The control's narrower frequency counter is what fails it in a
    read-only cell that fits (no eviction, so the weights never move): at
    a tiny size a 4-bit counter wraps where the 16-bit one would at the
    cell's size."""
    import dataclasses

    from bench import control
    from bench import reference as ref
    root = tiny.make_root(tmp_path, traffic="ycsb-c-fits", cell="tiny.c")
    monkeypatch.setattr(ref, "precision", lambda pool, c=False: (
        (dataclasses.replace(pool, freq_bits=4), ref.jnp.bfloat16) if c
        else (pool, ref.F32)))
    checks = control.readings("tiny.c", 1, rows=600, root=root)
    assert checks["slots_gap"]["value"] > checks["slots_gap"]["limit"]
    assert checks["weights_gap"]["value"] == 0
