"""Plain reference of the Ditto cache's sequential access semantics.

Written from the semantics the program documents (DESIGN.md and the
docstrings of ``core/cache.py`` and ``core/fc_cache.py``), in plain
``jax.numpy``, one round of C client lanes at a time, for the deployments
the benchmark runs: one tenant, uniform 64-byte objects, no near-cache,
GET and SET with read-through insert on a miss.  It imports nothing of the
program and takes nothing the program made: it builds its own empty pool
and its own client lanes from the seed and replays the whole request
history the program was given.

One round, in the order the program applies it:

1. probe: each request reads its bucket (``assoc`` slots); a live slot
   holding the key is a hit; a valid history entry (size 0xFF, age below
   the history length) holding the key's hash is a regret;
2. hit metadata: ``last_ts`` takes the round's clock; the ``freq`` count
   goes through the lane's frequency-counter cache (flush at the
   threshold, or when an entry is replaced);
3. expert weights: a regret multiplies the lane's local weights by
   ``exp(-lr * d**age)`` on the experts of the entry's bitmap and buffers
   the penalty; a lane with ``sync_period`` buffered regrets syncs, and
   the global weights are renormalised every round;
4. insert on a miss: the first request of each bucket in lane order wins;
   it takes the bucket's first free slot, else its oldest valid history
   entry, else its lowest-priority live object under the lane's expert;
5. sampled eviction while the pool is over its budget: each charging
   insert reads ``window`` slots from a random offset, samples the first
   ``n_samples`` live ones and evicts the lowest-priority ones under its
   expert until its quota of the deficit is met; a victim becomes a
   history entry tagged with the experts that would also have chosen it.

The configuration states float32 expert weights and 32-bit frequency
counters; the benchmark's control runs the next narrower of each
(``weight_dtype`` bfloat16, ``Pool.freq_bits`` 16).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

U32, I32, F32 = jnp.uint32, jnp.int32, jnp.float32
EMPTY, HISTORY = 0, 0xFF
# the operation counters a round yields, in this order
COUNTERS = ("gets", "sets", "hits", "misses", "regrets", "evictions",
            "bucket_evictions", "insert_drops", "fc_hits", "fc_flushes",
            "weight_syncs")


@dataclasses.dataclass(frozen=True)
class Pool:
    """The semantics-bearing sizes of one memory node's pool."""

    n_buckets: int
    assoc: int
    capacity: int
    experts: tuple = ("lru", "lfu")
    n_samples: int = 5
    sample_window: int = 0
    hist_len: int = 0
    learning_rate: float = 0.1
    base_discount: float = 0.005
    sync_period: int = 100
    fc_size: int = 64
    fc_threshold: int = 10
    freq_bits: int = 32     # width of the frequency counter

    @property
    def n_slots(self) -> int:
        return self.n_buckets * self.assoc

    @property
    def history_len(self) -> int:
        return self.hist_len or self.capacity

    @property
    def window(self) -> int:
        return self.sample_window or 4 * self.n_samples

    @property
    def discount(self) -> float:
        # the history penalty d**age falls to base_discount at age capacity
        return float(self.base_discount) ** (1.0 / float(self.capacity))


# a slot: the atomic field (key, hash, size, history pointer) and the
# access metadata, one u32 column each
FIELDS = ("key", "key_hash", "size", "ptr", "insert_ts", "last_ts", "freq")


def precision(pool: Pool, control: bool = False):
    """The pool and the expert-weight dtype as the configuration states
    them, or, for the control, each a step narrower."""
    if not control:
        return pool, F32
    return dataclasses.replace(pool, freq_bits=16), jnp.bfloat16


class Table(NamedTuple):
    key: jax.Array
    key_hash: jax.Array
    size: jax.Array
    ptr: jax.Array
    insert_ts: jax.Array    # the expert bitmap while the slot is history
    last_ts: jax.Array
    freq: jax.Array
    n_cached: jax.Array
    bytes_cached: jax.Array
    hist_ctr: jax.Array
    clock: jax.Array
    weights: jax.Array
    written: jax.Array      # bool per slot: its record written since reset

    def column(self, name: str) -> jax.Array:
        return getattr(self, name)


class Lanes(NamedTuple):
    fc_slot: jax.Array
    fc_delta: jax.Array
    fc_ins: jax.Array
    local_w: jax.Array
    pen_acc: jax.Array
    pen_cnt: jax.Array
    rng: jax.Array


def empty_table(pool: Pool, weight_dtype=F32) -> Table:
    n, e = pool.n_slots, len(pool.experts)
    return Table(*[jnp.zeros((n,), U32) for _ in FIELDS],
                 jnp.zeros((), I32), jnp.zeros((), I32),
                 jnp.zeros((), U32), jnp.ones((), U32),
                 jnp.full((e,), 1.0 / e, weight_dtype), jnp.zeros((n,), bool))


def new_lanes(pool: Pool, n: int, seed: int, weight_dtype=F32) -> Lanes:
    f, e = pool.fc_size, len(pool.experts)
    return Lanes(jnp.full((n, f), -1, I32), jnp.zeros((n, f), U32),
                 jnp.zeros((n, f), U32), jnp.full((n, e), 1.0 / e, weight_dtype),
                 jnp.zeros((n, e), weight_dtype), jnp.zeros((n,), I32),
                 jax.random.split(jax.random.PRNGKey(seed), n))


def mix32(x):
    """splitmix32's finaliser: the key hash and, modulo n_buckets, the
    bucket."""
    x = x.astype(U32) + U32(0x9E3779B9)
    x = (x ^ (x >> 16)) * U32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * U32(0xC2B2AE35)
    return x ^ (x >> 16)


def _priority(name, last_ts, freq):
    """Eviction priority (lowest goes first) of the configured experts."""
    if name == "lru":
        return last_ts.astype(F32)
    if name == "lfu":
        return freq.astype(F32)
    raise ValueError(f"the reference knows the experts lru and lfu, not {name!r}")


def _first(mask, axis=-1):
    return jnp.argmax(mask, axis=axis)


def _first_per_value(values, valid):
    """True at the first valid occurrence of each value (lowest index)."""
    n = values.shape[0]
    key = jnp.where(valid, values.astype(I32), jnp.iinfo(I32).max)
    order = jnp.argsort(key, stable=True)
    k = key[order]
    lead = jnp.concatenate([jnp.ones((1,), bool), k[1:] != k[:-1]])
    return jnp.zeros((n,), bool).at[order].set(lead) & valid


def _fc_round(pool: Pool, ln: Lanes, slot, clock):
    """One round of the lanes' frequency-counter caches.  ``slot`` is the
    hit slot per lane (-1 for none).  Returns the lanes and the (slot,
    delta) increments that reach the table, with the counts of remote
    adds and of FC hits."""
    fs, fd, fi = ln.fc_slot, ln.fc_delta, ln.fc_ins
    f = fs.shape[1]
    col = jnp.arange(f)[None, :]
    act = slot >= 0
    match = (fs == slot[:, None]) & act[:, None]
    fc_hit = jnp.any(match, axis=1)
    at = col == _first(match, 1)[:, None]
    onehot = match & at
    delta = fd + onehot.astype(U32)
    full = onehot & (delta >= U32(pool.fc_threshold))
    flushed = jnp.any(full, axis=1)
    fi_ = _first(full, 1)
    out0 = jnp.where(flushed, fs[jnp.arange(fs.shape[0]), fi_], -1)
    d0 = jnp.where(flushed, delta[jnp.arange(fs.shape[0]), fi_], U32(0))
    # a miss installs the slot over the first empty entry, else the oldest
    empty = fs < 0
    age = jnp.where(empty, -jnp.inf, fi.astype(F32))
    vic = jnp.argmin(age, axis=1)
    rows = jnp.arange(fs.shape[0])
    install = act & ~fc_hit
    replaced = install & ~empty[rows, vic]
    out1 = jnp.where(replaced, fs[rows, vic], -1)
    d1 = jnp.where(replaced, delta[rows, vic], U32(0))
    fs = jnp.where(full, -1, fs)
    fd = jnp.where(full, U32(0), delta)
    put = install[:, None] & (col == vic[:, None])
    fs = jnp.where(put, slot[:, None], fs)
    fd = jnp.where(put, U32(1), fd)
    fi = jnp.where(put, clock, fi)
    ln = ln._replace(fc_slot=fs, fc_delta=fd, fc_ins=fi)
    adds_slot = jnp.concatenate([out0, out1])
    adds_delta = jnp.concatenate([d0, d1])
    return ln, adds_slot, adds_delta, jnp.sum(flushed) + jnp.sum(replaced), \
        jnp.sum(fc_hit)


def _write(column, idx, vals, combine="set"):
    """``column[idx[i]]`` set, added to or maxed with ``vals[i]`` for
    every i with ``idx[i] >= 0``."""
    at = jnp.where(idx >= 0, idx, column.shape[0])
    return getattr(column.at[at], combine)(vals, mode="drop")


def _live_size(size):
    return jnp.where((size != EMPTY) & (size != HISTORY), size, U32(0))


def _renormalise(w, pen, lr):
    w = w * jnp.exp(-lr * pen)
    w = jnp.maximum(w, 1e-4)
    return w / jnp.sum(w, axis=-1, keepdims=True)


def round_step(pool: Pool, tb: Table, ln: Lanes, keys, writes):
    """One round: ``keys`` u32[C] (0 = no request), ``writes`` bool[C].
    Returns the new table and lanes, the hit mask and the round's
    counters in ``COUNTERS`` order."""
    c = keys.shape[0]
    a, k, e, n = pool.assoc, pool.n_samples, len(pool.experts), pool.n_slots
    wdt = tb.weights.dtype
    clock = tb.clock
    op = keys != 0

    # 1. probe
    kh = mix32(keys)
    bucket = (kh % U32(pool.n_buckets)).astype(I32)
    bslots = bucket[:, None] * a + jnp.arange(a)[None, :]
    b_key, b_size = tb.key[bslots], tb.size[bslots]
    b_hash, b_ptr = tb.key_hash[bslots], tb.ptr[bslots]
    live = (b_size != EMPTY) & (b_size != HISTORY)
    is_hist = b_size == HISTORY
    age = (tb.hist_ctr - b_ptr).astype(U32)
    valid_hist = is_hist & (age < U32(pool.history_len))
    m = live & (b_key == keys[:, None]) & op[:, None]
    hit = jnp.any(m, axis=1)
    slot = jnp.where(hit, bslots[jnp.arange(c), _first(m, 1)], -1)
    hm = valid_hist & (b_hash == kh[:, None]) & op[:, None]
    regret = jnp.any(hm, axis=1) & ~hit
    hslot = bslots[jnp.arange(c), _first(hm, 1)]
    miss = op & ~hit

    # 2. hit metadata
    ln, fc_s, fc_d, n_flush, n_fc_hit = _fc_round(pool, ln, slot, clock)

    # 3. expert weights
    lr = jnp.asarray(pool.learning_rate, wdt)
    h_age = (tb.hist_ctr - tb.ptr[hslot]).astype(U32)
    pen = jnp.power(jnp.asarray(pool.discount, wdt), h_age.astype(wdt))
    bits = ((tb.insert_ts[hslot][:, None] >> jnp.arange(e)[None, :]) & 1
            ).astype(wdt)
    pen_e = jnp.where(regret[:, None], pen[:, None] * bits, 0.0).astype(wdt)
    local_w = ln.local_w * jnp.exp(-lr * pen_e)
    pen_acc = ln.pen_acc + pen_e
    pen_cnt = ln.pen_cnt + regret.astype(I32)
    sync = pen_cnt >= pool.sync_period
    total = jnp.sum(jnp.where(sync[:, None, None], pen_acc[:, None, :], 0.0),
                    axis=0)
    weights = _renormalise(tb.weights[None], total, lr)[0]
    local_w = jnp.maximum(jnp.where(sync[:, None], weights[None], local_w),
                          1e-4).astype(wdt)
    pen_acc = jnp.where(sync[:, None], 0.0, pen_acc).astype(wdt)
    pen_cnt = jnp.where(sync, 0, pen_cnt)
    ln = ln._replace(local_w=local_w, pen_acc=pen_acc, pen_cnt=pen_cnt)
    draw = jax.vmap(lambda r: jax.random.uniform(r, (2,)))(
        jax.vmap(jax.random.fold_in)(ln.rng, jnp.broadcast_to(clock, (c,))))
    share = local_w / jnp.maximum(jnp.sum(local_w, axis=-1, keepdims=True),
                                  1e-30)
    expert = jnp.sum((jnp.cumsum(share, axis=-1) < draw[:, :1]).astype(I32),
                     axis=-1)

    # 4. insert on a miss
    winner = _first_per_value(bucket, miss)
    free = (b_size == EMPTY) | (is_hist & ~valid_hist)
    has_free = jnp.any(free, axis=1)
    has_hist = jnp.any(valid_hist, axis=1)
    has_live = jnp.any(live, axis=1)
    b_prio = jnp.stack([_priority(x, tb.last_ts[bslots], tb.freq[bslots])
                        for x in pool.experts], axis=-1)
    b_prio = jnp.take_along_axis(b_prio, expert[:, None, None], axis=2)[..., 0]
    b_prio = jnp.where(live, b_prio, jnp.inf)
    oldest = jnp.where(valid_hist, age.astype(F32), -jnp.inf)
    rows = jnp.arange(c)
    plain = winner & has_free
    over_hist = winner & ~has_free & has_hist
    over_obj = winner & ~has_free & ~has_hist & has_live
    ins = plain | over_hist | over_obj
    ins_slot = jnp.where(
        plain, bslots[rows, _first(free, 1)],
        jnp.where(over_hist, bslots[rows, jnp.argmax(oldest, axis=1)],
                  bslots[rows, jnp.argmin(b_prio, axis=1)]))
    drops = (miss & ~winner) | (winner & ~ins)

    # 5. sampled eviction
    charged = plain | over_hist
    n_charged = jnp.sum(charged).astype(I32)
    over = tb.bytes_cached + n_charged - jnp.int32(pool.capacity)
    quota = jnp.where(over <= 0, 0,
                      jnp.maximum((over + jnp.maximum(n_charged, 1) - 1)
                                  // jnp.maximum(n_charged, 1), 1))
    evictor = charged & (over > 0)
    w_len = pool.window
    offset = jnp.minimum((draw[:, 1] * n).astype(I32), n - 1)
    samp = (offset[:, None] + jnp.arange(w_len)[None, :]) % n
    s_size = tb.size[samp]
    s_live = (s_size != EMPTY) & (s_size != HISTORY)
    sampled = s_live & (jnp.cumsum(s_live, axis=1) <= k)
    s_prio = jnp.stack([_priority(x, tb.last_ts[samp], tb.freq[samp])
                        for x in pool.experts], axis=-1)
    s_prio = jnp.where(sampled[:, :, None], s_prio, jnp.inf)
    cand = jnp.take_along_axis(samp, jnp.argmin(s_prio, axis=1), axis=1)
    ranked = jnp.take_along_axis(s_prio, expert[:, None, None], axis=2)[..., 0]
    freed = jnp.zeros((c,), F32)
    victims = []
    for _ in range(k):
        j = jnp.argmin(ranked, axis=1)
        low = ranked[rows, j]
        take = (freed < quota.astype(F32)) & (low < jnp.inf) & evictor
        victims.append(jnp.where(take, samp[rows, j], -1))
        freed = freed + jnp.where(take, 1.0, 0.0)
        ranked = ranked.at[rows, j].set(jnp.inf)
    victims = jnp.stack(victims, axis=1).reshape(-1)          # [C*K]
    evicted = _first_per_value(victims, victims >= 0)
    n_evict = jnp.sum(evicted).astype(I32)
    owner = jnp.repeat(rows, k)
    tag = jnp.sum((cand[owner] == victims[:, None]).astype(U32)
                  << jnp.arange(e, dtype=U32)[None, :], axis=1)
    tag = tag | (U32(1) << expert[owner].astype(U32))
    hist_id = tb.hist_ctr + (jnp.cumsum(evicted.astype(I32)) - 1).astype(U32)

    # the byte count moves by the live sizes of the slots this round writes
    touched = jnp.concatenate([jnp.where(hit & writes, slot, -1),
                               jnp.where(ins, ins_slot, -1),
                               jnp.where(evicted, victims, -1)])
    once = _first_per_value(touched, touched >= 0)
    idx = jnp.where(once, touched, 0)
    before = jnp.where(once, _live_size(tb.size[idx]).astype(I32), 0)

    # apply, every read of the old table done: hit metadata, SET sizes,
    # inserts, then evictions (size, pointer, bitmap)
    freq = _write(tb.freq, fc_s, fc_d, "add")
    if pool.freq_bits < 32:
        # a narrower counter wraps: rewrite the counts this round added to
        freq = _write(freq, fc_s, freq[jnp.maximum(fc_s, 0)]
                      & U32((1 << pool.freq_bits) - 1))
    clocks = jnp.broadcast_to(clock, keys.shape)
    last_ts = _write(tb.last_ts, jnp.where(hit, slot, -1), clocks, "max")
    size = _write(tb.size, jnp.where(hit & writes, slot, -1),
                  jnp.ones_like(keys))
    at = jnp.where(ins, ins_slot, -1)
    key = _write(tb.key, at, keys)
    key_hash = _write(tb.key_hash, at, kh)
    size = _write(size, at, jnp.ones_like(keys))
    ptr = _write(tb.ptr, at, jnp.zeros_like(keys))
    insert_ts = _write(tb.insert_ts, at, clocks)
    last_ts = _write(last_ts, at, clocks)
    freq = _write(freq, at, jnp.ones_like(keys))
    ev = jnp.where(evicted, victims, -1)
    size = _write(size, ev, jnp.full_like(tag, HISTORY))
    ptr = _write(ptr, ev, hist_id)
    insert_ts = _write(insert_ts, ev, tag)
    moved = jnp.sum(jnp.where(once, _live_size(size[idx]).astype(I32), 0)
                    - before)
    # a SET writes its record over the hit slot, an insert into its slot
    written = _write(tb.written, jnp.where(hit & writes, slot, -1),
                     jnp.ones_like(op))
    written = _write(written, at, jnp.ones_like(op))
    tb = Table(key, key_hash, size, ptr, insert_ts, last_ts, freq,
               tb.n_cached + n_charged - n_evict, tb.bytes_cached + moved,
               tb.hist_ctr + n_evict.astype(U32), clock + U32(1), weights,
               written)
    n_set = jnp.sum(op & writes)
    counts = jnp.stack([jnp.sum(op) - n_set, n_set, jnp.sum(hit),
                        jnp.sum(miss), jnp.sum(regret), n_evict,
                        jnp.sum(over_obj), jnp.sum(drops), n_fc_hit,
                        n_flush, jnp.sum(sync)]).astype(I32)
    return tb, ln, hit, counts


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1, 2))
def _replay_block(pool: Pool, tb: Table, ln: Lanes, keys, writes, active):
    """Replay a block of rounds; rounds with ``active`` False hold no
    request and leave the pool, its clock and its weights as they were."""
    def body(carry, xs):
        tb0, ln0 = carry
        kk, ww, on = xs
        tb1, ln1, hit, counts = round_step(pool, tb0, ln0, kk, ww)
        tb1 = tb1._replace(clock=jnp.where(on, tb1.clock, tb0.clock),
                           weights=jnp.where(on, tb1.weights, tb0.weights))
        return (tb1, ln1), (jnp.sum(hit).astype(I32), counts)

    (tb, ln), (hits, counts) = jax.lax.scan(body, (tb, ln),
                                           (keys, writes, active))
    return tb, ln, hits, jnp.sum(counts, axis=0)


def replay(pool: Pool, tb: Table, ln: Lanes, keys: np.ndarray,
           writes: np.ndarray, block: int = 256):
    """Replay the rounds of ``keys``/``writes`` ([R, C]) in blocks of
    ``block`` rounds (one compiled program per lane count).  Returns the
    table, the lanes, the hit count per round and the summed counters."""
    r, c = keys.shape
    hits, total = [], np.zeros(len(COUNTERS), np.int64)
    for s in range(0, r, block):
        kk = np.zeros((block, c), np.uint32)
        ww = np.zeros((block, c), bool)
        on = np.zeros((block,), bool)
        m = min(block, r - s)
        kk[:m], ww[:m], on[:m] = keys[s:s + m], writes[s:s + m], True
        tb, ln, h, cnt = _replay_block(pool, tb, ln, jnp.asarray(kk),
                                       jnp.asarray(ww), jnp.asarray(on))
        hits.append(np.asarray(h)[:m])
        total += np.asarray(cnt, np.int64)
    return tb, ln, (np.concatenate(hits) if hits else np.zeros(0, np.int32)), \
        dict(zip(COUNTERS, (int(x) for x in total)))
