"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Property tests are deterministic seed sweeps (the CI image has no
hypothesis; an importorskip here used to silently skip the whole
kernel suite)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hashing import bucket_of, hash_key
from repro.kernels import ops, ref

pytestmark = pytest.mark.fast

SEEDS = [11 * i + 3 for i in range(10)]


def make_table(rng, C, live_frac=0.4):
    """u32[C] slot columns: live objects of 1-8 blocks, some history
    entries (size 255), the rest empty."""
    size = np.zeros(C, np.uint32)
    n_live = int(C * live_frac)
    idx = rng.choice(C, n_live, replace=False)
    size[idx] = rng.integers(1, 9, n_live)
    size[rng.choice(C, C // 10, replace=False)] = 255
    ins = rng.integers(0, 1000, C).astype(np.uint32)
    last = rng.integers(0, 1000, C).astype(np.uint32)
    freq = rng.integers(1, 50, C).astype(np.uint32)
    return size, ins, last, freq


# ----------------------------------------------------------------------
# Quota-extended ranked eviction (the fused backend's hot loop).
# ----------------------------------------------------------------------

@pytest.mark.parametrize("C,W,B,experts,quota", [
    (512, 20, 8, ("lru", "lfu"), 1),
    (2048, 20, 13, ("lru", "lfu", "fifo", "size"), 3),   # odd B: padded
    (1024, 24, 32, ("hyperbolic", "lfu"), 5),
    # single-victim decisions (quota 1) across expert sets and windows,
    # including the widest window a kernel row pair holds (W = 128)
    (512, 20, 8, ("fifo", "lfu"), 1),
    (2048, 20, 32, ("lru", "lfu"), 1),
    (2048, 12, 16, ("lru", "lfu", "fifo", "size"), 1),
    (4096, 128, 64, ("hyperbolic", "lfu"), 1),
])
def test_ranked_eviction_matches_ref(rng, C, W, B, experts, quota):
    size, ins, last, freq = make_table(rng, C, live_frac=0.5)
    offs = rng.integers(0, C, B).astype(np.int32)
    offs[0] = C - 1                  # a window that wraps to slot 0
    choice = rng.integers(0, len(experts), B).astype(np.int32)
    must = rng.random(B) < 0.7
    ts = rng.integers(900, 1100, B).astype(np.float32)  # per-op clocks
    v1, c1 = ops.ranked_eviction_op(size, ins, last, freq, offs, choice,
                                    must, quota, ts, window=W,
                                    experts=experts)
    v2, c2 = ref.ranked_eviction_ref(
        jnp.asarray(size), jnp.asarray(ins), jnp.asarray(last),
        jnp.asarray(freq), jnp.asarray(offs), jnp.asarray(choice),
        jnp.asarray(must), quota, jnp.asarray(ts), window=W, k=5,
        experts=experts)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


@pytest.mark.parametrize("seed", SEEDS[:6])
@pytest.mark.parametrize("quota", [0, 1, 3, 5, 17])
def test_ranked_eviction_properties(seed, quota):
    """Victims are exactly the shortest chosen-expert-ranked prefix of
    the sample whose summed sizes (64B blocks) cover the block quota —
    at most K victims — for evicting ops, and none for the rest."""
    rng = np.random.default_rng(seed)
    C, W, K, B = 512, 20, 5, 16
    experts = ("lru", "lfu")
    size, ins, last, freq = make_table(rng, C, live_frac=0.3)
    offs = rng.integers(0, C, B).astype(np.int32)
    choice = rng.integers(0, 2, B).astype(np.int32)
    must = rng.random(B) < 0.8
    v, _ = ops.ranked_eviction_op(size, ins, last, freq, offs, choice,
                                  must, quota,
                                  np.full(B, 1000.0, np.float32),
                                  window=W, k=K, experts=experts)
    v = np.asarray(v)
    assert v.shape == (B, K)
    pr_tab = {"lru": last, "lfu": freq}
    for b in range(B):
        idx = np.arange(offs[b], offs[b] + W) % C
        live = (size[idx] > 0) & (size[idx] < 255)
        in_sample = live & (np.cumsum(live) <= K)
        pr = pr_tab[experts[choice[b]]][idx].astype(np.float64).copy()
        pr[~in_sample] = np.inf
        expect, freed = [], 0.0
        if must[b]:
            for j in np.argsort(pr, kind="stable"):
                if not in_sample[j] or freed >= quota or len(expect) >= K:
                    break
                expect.append(int(idx[j]))
                freed += float(size[idx][j])
        taken = [int(x) for x in v[b][v[b] >= 0]]
        assert taken == expect, (b, taken, expect)


def test_ranked_eviction_unit_sizes_recover_count_quota():
    """With uniform 1-block objects the block quota degenerates to the
    old take-`quota`-victims rule exactly."""
    rng = np.random.default_rng(0)
    C, W, K, B = 512, 20, 5, 16
    size, ins, last, freq = make_table(rng, C, live_frac=0.4)
    size[size > 0] = np.where(size[size > 0] < 255, 1, size[size > 0])
    offs = rng.integers(0, C, B).astype(np.int32)
    choice = rng.integers(0, 2, B).astype(np.int32)
    must = np.ones(B, bool)
    for quota in (1, 3, 5):
        v, _ = ops.ranked_eviction_op(
            size, ins, last, freq, offs, choice, must, quota,
            np.full(B, 1000.0, np.float32), window=W, k=K,
            experts=("lru", "lfu"))
        v = np.asarray(v)
        for b in range(B):
            idx = np.arange(offs[b], offs[b] + W) % C
            live = (size[idx] > 0) & (size[idx] < 255)
            n_samp = min(int(live.sum()), K)
            assert (v[b] >= 0).sum() == min(quota, n_samp)


def test_ranked_eviction_empty_table(rng):
    """Nothing live: no op claims a victim, whatever its quota."""
    C, W, B = 512, 20, 8
    size = np.zeros(C, np.uint32)
    ins = last = freq = np.ones(C, np.uint32)
    offs = rng.integers(0, C, B).astype(np.int32)
    v, _ = ops.ranked_eviction_op(size, ins, last, freq, offs,
                                  np.zeros(B, np.int32), np.ones(B, bool),
                                  5, np.full(B, 10.0, np.float32), window=W)
    assert (np.asarray(v) == -1).all()


def test_ranked_eviction_zero_quota_is_noop(rng):
    C, W, B = 512, 20, 8
    size, ins, last, freq = make_table(rng, C)
    offs = rng.integers(0, C, B).astype(np.int32)
    v, _ = ops.ranked_eviction_op(size, ins, last, freq, offs,
                                  np.zeros(B, np.int32), np.ones(B, bool),
                                  0, np.full(B, 10.0, np.float32), window=W)
    assert (np.asarray(v) == -1).all()


# ----------------------------------------------------------------------
# Fused Get-path probe: bucket match + embedded-history match.
# ----------------------------------------------------------------------

def make_probe_table(rng, C, A, hist_ctr=1000, hist_len=256):
    """Random table with live, history, and empty slots."""
    tk = np.zeros(C, np.uint32)
    tsz = np.zeros(C, np.uint32)
    th = np.zeros(C, np.uint32)
    tp = np.zeros(C, np.uint32)
    put = rng.integers(1, 1 << 31, C // 2).astype(np.uint32)
    hs = np.asarray(hash_key(jnp.asarray(put)))
    bs = np.asarray(bucket_of(jnp.asarray(hs), C // A))
    live_keys, hist_keys = [], []
    for k, h, b in zip(put, hs, bs):
        for a in range(A):
            s = b * A + a
            if tsz[s] == 0:
                kind = rng.integers(0, 3)
                if kind == 0:                      # live object
                    tk[s], tsz[s], th[s] = k, 1, h
                    live_keys.append(k)
                else:                              # history entry
                    tsz[s], th[s] = 255, h
                    age = rng.integers(0, 2 * hist_len)
                    tp[s] = np.uint32(hist_ctr - age)
                    if age < hist_len:
                        hist_keys.append(k)
                break
    return tk, tsz, th, tp, live_keys, hist_keys


@pytest.mark.parametrize("C,A,B", [(2048, 8, 16), (1024, 4, 13),
                                   (512, 8, 16), (4096, 8, 32), (1024, 4, 8)])
def test_access_probe_matches_ref(rng, C, A, B):
    hist_ctr, hist_len = 1000, 128
    tk, tsz, th, tp, live_keys, hist_keys = make_probe_table(
        rng, C, A, hist_ctr, hist_len)
    pool = (live_keys[:B // 3] + hist_keys[:B // 3]
            + list(rng.integers(1, 1 << 31, B).astype(np.uint32)))
    q = np.array(pool[:B], np.uint32)
    r1 = ops.access_probe_op(tk, tsz, th, tp, q, hist_ctr, assoc=A,
                             history_len=hist_len)
    r2 = ref.access_probe_ref(jnp.asarray(tk), jnp.asarray(tsz),
                              jnp.asarray(th), jnp.asarray(tp),
                              jnp.asarray(q), hist_ctr, assoc=A,
                              history_len=hist_len)
    for a, b, what in zip(r1, r2, ("found", "slot", "hfound", "hslot")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), what)
    assert int(np.asarray(r1[0]).sum()) >= min(len(live_keys), B // 3)


def test_access_probe_expired_history_misses(rng):
    """History entries older than history_len must not match."""
    C, A = 512, 8
    hist_ctr = 5000
    tk, tsz, th, tp, _, _ = make_probe_table(rng, C, A, hist_ctr, 1)
    key = np.uint32(77)
    h = np.asarray(hash_key(jnp.asarray(key[None])))[0]
    b = int(np.asarray(bucket_of(jnp.asarray(h[None]), C // A))[0])
    s = b * A
    tsz[s], th[s] = 255, h
    tp[s] = np.uint32(hist_ctr - 400)          # age 400 >= hist_len 64
    found, slot, hf, _ = ops.access_probe_op(
        tk, tsz, th, tp, np.array([key]), hist_ctr, assoc=A, history_len=64)
    assert not bool(np.asarray(hf)[0]) and not bool(np.asarray(found)[0])
    tp[s] = np.uint32(hist_ctr - 3)            # fresh again
    _, _, hf2, hs2 = ops.access_probe_op(
        tk, tsz, th, tp, np.array([key]), hist_ctr, assoc=A, history_len=64)
    assert bool(np.asarray(hf2)[0]) and int(np.asarray(hs2)[0]) == s


def test_access_probe_odd_batch(rng):
    """B not divisible by block_b: padded internally, no crash."""
    C, A, B = 512, 8, 11
    z = np.zeros(C, np.uint32)
    q = rng.integers(1, 1 << 31, B).astype(np.uint32)
    f, s, hf, _ = ops.access_probe_op(z, z, z, z, q, 0, assoc=A)
    assert f.shape == (B,) and s.shape == (B,)
    assert not np.asarray(f).any() and not np.asarray(hf).any()


# ----------------------------------------------------------------------
# Fused hit-side metadata update (last_ts + ext + combining freq FAA).
# ----------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_hit_metadata_update_property(seed):
    rng = np.random.default_rng(seed)
    C, Bh, Be = 1024, 24, 16
    freq = rng.integers(0, 100, C).astype(np.uint32)
    last = rng.integers(0, 100, C).astype(np.uint32)
    ext = rng.random((C, 4)).astype(np.float32) * 100
    hits = rng.integers(-1, C, Bh).astype(np.int32)
    emits = rng.integers(-1, C, Be).astype(np.int32)
    deltas = rng.integers(1, 10, Be).astype(np.uint32)
    hts = rng.integers(700, 800, Bh).astype(np.uint32)  # per-hit clocks
    _check_hit_metadata(freq, last, ext, hits, hts, emits, deltas)


def _check_hit_metadata(freq, last, ext, hits, hts, emits, deltas):
    r1 = ops.hit_metadata_update_op(freq, last, ext, hits, hts, emits,
                                    deltas)
    r2 = ref.hit_metadata_update_ref(
        jnp.asarray(freq.astype(np.float32)), jnp.asarray(last),
        jnp.asarray(ext), jnp.asarray(hits), jnp.asarray(hts),
        jnp.asarray(emits), jnp.asarray(deltas.astype(np.float32)))
    np.testing.assert_array_equal(np.asarray(r1[0]),
                                  np.asarray(r2[0]).astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(r1[1]), np.asarray(r2[1]))
    np.testing.assert_allclose(np.asarray(r1[2]), np.asarray(r2[2]),
                               atol=1e-5, rtol=1e-6)


def test_hit_metadata_update_odd_table(rng):
    """Table size not divisible by block_c: padded internally."""
    C = 768  # not a multiple of 512
    freq = np.zeros(C, np.uint32)
    last = np.zeros(C, np.uint32)
    ext = np.zeros((C, 4), np.float32)
    hits = np.array([7, 700, -1], np.int32)
    f2, l2, e2 = ops.hit_metadata_update_op(
        freq, last, ext, hits, np.full(3, 9, np.uint32),
        np.array([700, 700], np.int32), np.array([2, 3], np.uint32))
    assert f2.shape == (C,) and l2.shape == (C,) and e2.shape == (C, 4)
    assert float(f2[700]) == 5.0 and float(l2[700]) == 9.0
    assert float(l2[7]) == 9.0 and float(f2[7]) == 0.0
    assert float(e2[7, 1]) == 9.0  # LRU-K ring slot (freq+1) % 2 == 1


@pytest.mark.parametrize("seed", SEEDS)
def test_hit_metadata_update_faa_property(seed):
    """Flush-heavy batches with duplicate slots and timestamps past 2**31
    (the u32 max runs on sign-flipped i32 bits inside the kernel)."""
    rng = np.random.default_rng(seed)
    C, Bh, Be = 1024, 32, 160
    freq = rng.integers(0, 100, C).astype(np.uint32)
    last = rng.integers(2**31 - 50, 2**31 + 50, C).astype(np.uint32)
    ext = rng.random((C, 4)).astype(np.float32)
    hits = rng.integers(-1, 64, Bh).astype(np.int32)      # many duplicates
    emits = rng.integers(-1, 64, Be).astype(np.int32)
    deltas = rng.integers(1, 10, Be).astype(np.uint32)
    hts = rng.integers(2**31 - 40, 2**31 + 60, Bh).astype(np.uint32)
    _check_hit_metadata(freq, last, ext, hits, hts, emits, deltas)


def test_hit_metadata_update_combines_duplicates():
    freq = np.zeros(512, np.uint32)
    last = np.zeros(512, np.uint32)
    ext = np.zeros((512, 4), np.float32)
    slots = np.array([7, 7, 7, -1, 9, 9, 3, 3], np.int32)
    f2, l2, _ = ops.hit_metadata_update_op(
        freq, last, ext, slots, np.array([5, 4, 3, 0, 2, 2, 1, 1], np.uint32),
        slots, np.ones(8, np.uint32))
    assert int(f2[7]) == 3 and int(f2[9]) == 2 and int(f2[3]) == 2
    assert int(l2[7]) == 5 and int(l2[9]) == 2 and int(l2[0]) == 0


@pytest.mark.parametrize("x", [0, 1, 0xFFFF, 0x10000, 2**24 + 1, 2**31 - 1,
                               2**31, 2**31 + 129, 2**32 - 1])
def test_u32_to_f32_exact(x):
    """The kernels' in-register u32 -> f32 conversion rounds exactly as
    XLA's unsigned convert does (the reference path's conversion)."""
    from repro.kernels.runtime import u32_to_f32
    u = jnp.asarray([x], jnp.uint32)
    got = u32_to_f32(jax.lax.bitcast_convert_type(u, jnp.int32))
    assert np.asarray(got)[0] == np.asarray(u.astype(jnp.float32))[0]


@pytest.mark.parametrize("b,t,h,d,bq,bk,dtype", [
    (2, 256, 4, 64, 128, 128, jnp.float32),
    (1, 512, 2, 128, 128, 64, jnp.float32),
    (2, 128, 3, 32, 64, 128, jnp.float32),
    (2, 256, 2, 64, 128, 128, jnp.bfloat16),
])
def test_flash_attention_matches_oracle(b, t, h, d, bq, bk, dtype):
    from repro.kernels.flash_attention import flash_attention
    from repro.models.attention import full_attention
    k0 = jax.random.PRNGKey(0)
    q = jax.random.normal(k0, (b, t, h, d)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(k0, 1), (b, t, h, d)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(k0, 2), (b, t, h, d)).astype(dtype)
    o1 = flash_attention(q, k, v, blk_q=bq, blk_k=bk).astype(jnp.float32)
    o2 = full_attention(q, k, v).astype(jnp.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=tol, rtol=tol)
