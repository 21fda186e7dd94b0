"""Duplicate resolution and the eviction write-back of ``access_group``
against the versions that take a table the size of the domain and a
scatter over every entry: the pairwise dedup and the compacted write-back
must give the same bits."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CacheConfig, init_cache, init_clients, init_stats
from repro.core import cache as cache_mod
from repro.core.cache import _first_winner, _set_live

I32 = jnp.int32
U32 = jnp.uint32


def _table_first_winner(x, valid, domain):
    """The scatter-min dedup over a ``domain + 1`` table, as the engine
    ran it before the pairwise compare; any ``domain`` above every value
    gives the same result."""
    B = x.shape[0]
    pos = jnp.arange(B, dtype=I32)
    tgt = jnp.where(valid, x.astype(I32), domain)
    best = jnp.full((domain + 1,), B, I32).at[tgt].min(pos)
    return valid & (best[jnp.where(valid, x.astype(I32), 0)] == pos)


def _full_width_set(cols, idx, vals, live, chunk):
    """One scatter per column over every entry, the dead ones dropped."""
    tgt = jnp.where(live, idx, cols[0].shape[0])
    return tuple(c.at[tgt].set(v, mode="drop") for c, v in zip(cols, vals))


def _dedup_case(name, n, domain, rng):
    if name == "all_invalid":
        return rng.integers(0, domain, n), np.zeros(n, bool)
    if name == "all_equal":
        return np.full(n, domain // 2), np.ones(n, bool)
    if name == "distinct":
        return rng.permutation(domain)[:n], np.ones(n, bool)
    if name == "edges":       # only 0 and domain - 1, some lanes invalid
        return rng.choice([0, domain - 1], n), rng.random(n) < 0.7
    if name == "earliest_invalid":
        # Every value twice; the first copy is invalid, so the second wins.
        x = np.repeat(rng.permutation(domain)[:(n + 1) // 2], 2)[:n]
        return x, np.arange(n) % 2 == 1
    x = rng.integers(0, max(2, n // 3), n)      # "mixed": many duplicates
    return x, rng.random(n) < 0.6


@pytest.mark.parametrize("n", [1, 7, 256, 1280, 2048, 2049, 5000,
                               20480])          # 20480: the fill's victims
@pytest.mark.parametrize("case", ["all_invalid", "all_equal", "distinct",
                                  "edges", "earliest_invalid", "mixed"])
def test_first_winner_matches_the_table(n, case):
    domain = 3 * n + 5
    x, valid = _dedup_case(case, n, domain, np.random.default_rng(n))
    for dt in (jnp.int32, jnp.uint32):
        args = (jnp.asarray(x, dt), jnp.asarray(valid))
        got = np.asarray(_first_winner(*args))
        want = np.asarray(_table_first_winner(*args, domain))
        np.testing.assert_array_equal(got, want)
    if case == "earliest_invalid":
        assert got.sum() == valid.sum()


B, K = 64, 5
CHUNK = max(8, B // 4)


@pytest.mark.parametrize("n,n_live", [
    (B * K, 0), (B * K, 1), (B * K, CHUNK), (B * K, CHUNK + 1),
    (B * K, B * K), (4096, 300)])
def test_set_live_matches_the_full_width_scatter(n, n_live):
    rng = np.random.default_rng(n_live)
    slots = 4 * n
    idx = rng.permutation(slots)[:n].astype(np.int32)
    live = np.zeros(n, bool)
    live[rng.permutation(n)[:n_live]] = True
    idx = np.where(live, idx, -1)        # dead entries hold -1, as victims do
    cols = tuple(jnp.asarray(rng.integers(0, 2**32, slots, dtype=np.uint32))
                 for _ in range(3))
    vals = tuple(jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
                 for _ in range(3))
    args = (cols, jnp.asarray(idx), vals, jnp.asarray(live))
    got = jax.jit(_set_live, static_argnames="chunk")(*args, chunk=CHUNK)
    want = _full_width_set(*args, chunk=CHUNK)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _step(cfg):
    """A fresh jit of one step: it traces with the module's functions as
    they are when it is first called."""
    return jax.jit(functools.partial(cache_mod.access_group, cfg))


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_access_group_step_that_evicts_k_per_op_is_unchanged(
        backend, monkeypatch):
    """One step of inserts on a full pool whose budget was shrunk far
    below its content, so each inserting op's quota asks for all K victims
    and the write-back loop runs several times: state, counters and result
    are the bits the table dedup and the full-width write-back give."""
    cfg = CacheConfig(n_buckets=256, assoc=8, capacity=1024,
                      experts=("lru", "lfu"), n_samples=K, backend=backend)
    rng = np.random.default_rng(7)
    state, clients, stats = init_cache(cfg), init_clients(cfg, B), init_stats()
    fill = rng.permutation(np.arange(1, 10**6))[:40 * B].reshape(40, B)
    step = _step(cfg)
    for row in fill:
        state, clients, stats, _ = step(state, clients, stats,
                                        jnp.asarray(row[None], U32))
    state = state._replace(capacity_blocks=jnp.int32(B))
    keys = jnp.asarray(np.arange(10**6 + 1, 10**6 + 1 + B)[None], U32)

    got = step(state, clients, stats, keys)
    with monkeypatch.context() as m:
        m.setattr(cache_mod, "_first_winner", functools.partial(
            _table_first_winner, domain=cfg.n_slots))
        m.setattr(cache_mod, "_set_live", _full_width_set)
        want = _step(cfg)(state, clients, stats, keys)

    evicted = int(got[2].evictions - stats.evictions)
    assert evicted > 2 * CHUNK                 # three trips or more
    assert int(jnp.sum(got[3].evicted)) > B // 2
    for path, g, w in zip(
            jax.tree_util.tree_leaves_with_path(want),
            jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path[0]))
