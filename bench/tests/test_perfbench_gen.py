"""The benchmark's copy of the YCSB generator: the program's algorithm,
deterministic per seed, with the workloads' read/write mixes."""

import numpy as np
import pytest

import _perfbench_tiny  # noqa: F401  (puts the repo on sys.path)
from bench import gen, traffic


def test_same_seed_same_trace():
    a = gen.ycsb("A", 50_000, n_keys=100_000, seed=9)
    b = gen.ycsb("A", 50_000, n_keys=100_000, seed=9)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_other_seed_other_trace():
    a, _ = gen.ycsb("A", 50_000, n_keys=100_000, seed=9)
    b, _ = gen.ycsb("A", 50_000, n_keys=100_000, seed=10)
    assert not np.array_equal(a, b)


def test_ycsb_a_writes_half():
    keys, w = gen.ycsb("A", 200_000, n_keys=100_000, seed=1)
    assert abs(w.mean() - 0.5) < 0.01
    assert keys.min() >= 1 and keys.max() <= 100_000


def test_ycsb_c_reads_only():
    _, w = gen.ycsb("C", 100_000, n_keys=100_000, seed=1)
    assert not w.any()


@pytest.mark.parametrize("n_keys,theta", [(1000, 0.99), (50_000, 0.99),
                                          (3000, 0.5)])
def test_zipf_ranks_are_numpys_choice(n_keys, theta):
    """The guide-table sampler returns what Generator.choice(p=...) does
    for the same seed: the original generator's stream."""
    p = np.arange(1, n_keys + 1, dtype=np.float64) ** (-theta)
    p /= p.sum()
    want = np.random.default_rng(5).choice(n_keys, size=20_000, p=p)
    got = gen._ranks(np.random.default_rng(5), 20_000, n_keys, theta)
    assert np.array_equal(got, want)


def test_fill_keys_are_distinct_and_in_the_key_space():
    mix = dict(ycsb="A", theta=0.99, keys_per_node=5000,
               fill={"distinct_sets_per_capacity": 1.25}, rounds_per_call=8,
               warmup_calls=2, max_ops_per_s_per_node=1000,
               hit_rate_ops_per_node=100, trace_calls=2)
    tr = traffic.build(mix, nodes=1, capacity=1024, lanes=16, fill_lanes=64,
                       seconds=2, seed=2**33 + 5)
    k = tr.fill_keys[tr.fill_keys != 0]
    assert len(k) == 1280 and len(np.unique(k)) == 1280
    assert k.max() <= 5000
    assert tr.keys.shape[1] == 16 and tr.keys.shape[0] % 8 == 0
    assert tr.keys.shape[0] * 16 >= 2000 + 2 * 8 * 16
