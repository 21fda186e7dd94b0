"""Plain reference of Ditto memory nodes behind the client-side router.

Written from the semantics the program documents for its disaggregated
deployment (DESIGN.md §2 and §14, Ditto's DM architecture: clients hash a
key to the memory node that owns its bucket and talk to that node alone),
in plain ``jax.numpy``.  It imports nothing of the program: each node is
``bench/reference.py``'s pool and round (``round_step``), and this file
adds what lies between the nodes.  A run of S nodes, one round at a time:

1. routing: the global pool has ``n_buckets`` buckets; a key's bucket is
   ``mix32(key) % n_buckets`` and its owner is that bucket over the
   node's share, ``bucket // (n_buckets / S)``.  Each node holds the
   buckets it owns as a pool of its own (a key's bucket there is
   ``mix32(key) % (n_buckets / S)``), with 1/S of the capacity.  No
   bucket is replicated and every node serves;
2. packing: the round's ``S * L`` requests come from S sources of L lanes
   (source s sends lanes ``[s * L, (s + 1) * L)``).  Each source packs its
   requests by owner, then lane, into ``q`` positions a destination, ``q =
   min(L, route_factor * L // S + 1)``; a request past ``q`` is not
   executed and counts as a route drop;
3. each node runs one round over its ``S * q`` positions, position ``s * q
   + r`` holding the r-th request source s sent it (none where it sent
   fewer), on persistent client lanes of its own: the node holds L lanes
   for each source, and position ``s * q + r`` is lane r of source s's
   block.  Lanes carry their FC cache, penalty buffers, local weights and
   random stream from round to round;
4. answers: a request's hit is its node's answer at its position;
5. lazy weight sync across nodes, after every round: when the lanes of
   any one node hold ``sync_period`` or more buffered regrets between
   them, every node applies the penalties buffered on all nodes' lanes
   (each node's lanes summed as a fixed pairwise tree, the nodes' sums
   added in node order: floating-point addition does not associate, and
   the order is part of what the nodes agree on) to its own weights
   (multiply by ``exp(-lr * penalty)``, clamp at 1e-4, normalise), and
   every lane adopts its node's new weights with empty buffers.

Departures from the paper, each the program's as DESIGN.md states it:
the paper's clients keep their own state and fetch weights from one
controller; here a node keeps the state of the client positions it
serves (a client's FC cache holds slots of one node's table, so it is
kept per node), the sync fires on a node's total, not on one client's
buffer, and the nodes apply the same update to weights kept per node.
The paper's route capacity is unbounded; the program's is ``q``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref
from bench.reference import COUNTERS, I32, U32


@dataclasses.dataclass(frozen=True)
class Cluster:
    """The semantics-bearing sizes of S nodes: the global pool, the node
    count and the router's capacity factor."""

    pool: ref.Pool          # the GLOBAL pool
    n_nodes: int
    route_factor: int = 4

    @property
    def node(self) -> ref.Pool:
        """One node's pool: 1/S of the buckets and the capacity."""
        return dataclasses.replace(
            self.pool, n_buckets=self.pool.n_buckets // self.n_nodes,
            capacity=self.pool.capacity // self.n_nodes)

    def capacity_q(self, lanes: int) -> int:
        """Positions a source may fill at one destination in a round."""
        if self.route_factor <= 0:
            return lanes
        return max(1, min(lanes, self.route_factor * lanes
                          // self.n_nodes + 1))


def empty_tables(cl: Cluster, weight_dtype=ref.F32) -> ref.Table:
    """S empty node tables, stacked on a leading node axis."""
    one = ref.empty_table(cl.node, weight_dtype)
    return jax.tree.map(lambda x: jnp.stack([x] * cl.n_nodes), one)


def new_lanes(cl: Cluster, lanes: int, seed: int,
              weight_dtype=ref.F32) -> ref.Lanes:
    """Every node's client lanes, ``lanes`` a source: node d holds lanes
    ``[d * S * lanes, (d + 1) * S * lanes)`` of one seeded set, in blocks
    of one source each.  Stacked on a leading node axis."""
    s = cl.n_nodes
    every = ref.new_lanes(cl.node, s * s * lanes, seed, weight_dtype)
    return jax.tree.map(lambda x: x.reshape((s, s * lanes) + x.shape[1:]),
                        every)


def route(cl: Cluster, keys, writes, q: int):
    """Step 2: the positions of one round.  Returns each node's keys and
    write flags ([S, S * q]), each request's (node, position) with ``ok``
    where it is executed, and the number of route drops."""
    s = cl.n_nodes
    lanes = keys.shape[0] // s
    k, w = keys.reshape(s, lanes), writes.reshape(s, lanes)
    live = k != 0
    share = cl.pool.n_buckets // s
    owner = ((ref.mix32(k) % U32(cl.pool.n_buckets)).astype(I32)
             // share)                                      # [S, L]
    mine = (owner[..., None] == jnp.arange(s)) & live[..., None]
    rank = jnp.take_along_axis(jnp.cumsum(mine, axis=1) - 1,
                               owner[..., None] % s, axis=2)[..., 0]
    ok = live & (rank < q)
    pos = jnp.arange(s)[:, None] * q + rank
    node = jnp.where(ok, owner, s)
    nk = jnp.zeros((s, s * q), U32).at[node, pos].set(k, mode="drop")
    nw = jnp.zeros((s, s * q), bool).at[node, pos].set(w, mode="drop")
    return nk, nw, node, pos, ok, jnp.sum(live & ~ok).astype(I32)


def _lanes_at(ln: ref.Lanes, s: int, q: int) -> ref.Lanes:
    """One node's lanes of its ``s * q`` positions: lanes [0, q) of every
    source block."""
    held = ln.fc_slot.shape[0] // s
    return jax.tree.map(lambda x: x.reshape((s, held) + x.shape[1:])[:, :q]
                        .reshape((s * q,) + x.shape[1:]), ln)


def _lanes_back(ln: ref.Lanes, at: ref.Lanes, s: int, q: int) -> ref.Lanes:
    held = ln.fc_slot.shape[0] // s
    return jax.tree.map(
        lambda x, y: x.reshape((s, held) + x.shape[1:]).at[:, :q].set(
            y.reshape((s, q) + y.shape[1:])).reshape(x.shape), ln, at)


def pairwise_sum(x):
    """``x`` summed over its leading axis as a fixed tree: padded with
    zeros to a power of two rows, then adjacent rows added level by
    level."""
    n = x.shape[0]
    width = 1 << max(n - 1, 0).bit_length()
    x = jnp.concatenate([x, jnp.zeros((width - n,) + x.shape[1:], x.dtype)])
    while x.shape[0] > 1:
        x = x[0::2] + x[1::2]
    return x[0]


def sync(cl: Cluster, tb: ref.Table, ln: ref.Lanes):
    """Step 5 over the stacked nodes."""
    wdt = tb.weights.dtype
    fire = jnp.any(jnp.sum(ln.pen_cnt, axis=1) >= cl.pool.sync_period)
    total = functools.reduce(lambda a, b: a + b, [
        pairwise_sum(ln.pen_acc[d]) for d in range(cl.n_nodes)])
    lr = jnp.asarray(cl.pool.learning_rate, wdt)
    w = tb.weights * jnp.exp(-lr * total)[None]
    w = jnp.maximum(w, jnp.asarray(1e-4, wdt))
    w = (w / jnp.sum(w, axis=-1, keepdims=True)).astype(wdt)
    w = jnp.where(fire, w, tb.weights)
    ln = ln._replace(
        local_w=jnp.where(fire, jnp.broadcast_to(w[:, None],
                                                 ln.local_w.shape),
                          ln.local_w),
        pen_acc=jnp.where(fire, jnp.zeros_like(ln.pen_acc), ln.pen_acc),
        pen_cnt=jnp.where(fire, jnp.zeros_like(ln.pen_cnt), ln.pen_cnt))
    return tb._replace(weights=w), ln


def round_all(cl: Cluster, q: int, tb: ref.Table, ln: ref.Lanes, keys,
              writes):
    """One round of the whole cluster: ``keys``/``writes`` [S * L].
    Returns the tables, the lanes, each request's hit and the round's
    counters (``COUNTERS`` order) with its route drops."""
    s = cl.n_nodes
    nk, nw, node, pos, ok, drops = route(cl, keys, writes, q)
    at = jax.vmap(lambda x: _lanes_at(x, s, q))(ln)
    tb, at, hit, counts = jax.vmap(
        functools.partial(ref.round_step, cl.node))(tb, at, nk, nw)
    ln = jax.vmap(lambda x, y: _lanes_back(x, y, s, q))(ln, at)
    tb, ln = sync(cl, tb, ln)
    hits = jnp.where(ok, hit[jnp.minimum(node, s - 1), pos], False)
    return tb, ln, hits.reshape(-1), jnp.sum(counts, axis=0), drops


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def _replay_block(cl: Cluster, q: int, tb, ln, keys, writes, active):
    """Replay a block of rounds; rounds with ``active`` False hold no
    request and leave every node as it was."""
    def body(carry, xs):
        tb0, ln0 = carry
        kk, ww, on = xs
        tb1, ln1, hits, counts, drops = round_all(cl, q, tb0, ln0, kk, ww)
        keep = lambda a, b: jnp.where(on, a, b)
        tb1 = jax.tree.map(keep, tb1, tb0)
        ln1 = jax.tree.map(keep, ln1, ln0)
        return (tb1, ln1), (jnp.sum(hits).astype(I32),
                            jnp.where(on, counts, 0), jnp.where(on, drops, 0))

    (tb, ln), (hits, counts, drops) = jax.lax.scan(
        body, (tb, ln), (keys, writes, active))
    return tb, ln, hits, jnp.sum(counts, axis=0), jnp.sum(drops)


def replay(cl: Cluster, tb: ref.Table, ln: ref.Lanes, keys: np.ndarray,
           writes: np.ndarray, block: int = 256):
    """Replay the rounds of ``keys``/``writes`` ([R, S * L]) in blocks of
    ``block`` rounds.  Returns the tables, the lanes, the hit count per
    round and the summed counters, ``route_drops`` among them."""
    r, width = keys.shape
    q = cl.capacity_q(width // cl.n_nodes)
    hits, total, dropped = [], np.zeros(len(COUNTERS), np.int64), 0
    for s in range(0, r, block):
        kk = np.zeros((block, width), np.uint32)
        ww = np.zeros((block, width), bool)
        on = np.zeros((block,), bool)
        m = min(block, r - s)
        kk[:m], ww[:m], on[:m] = keys[s:s + m], writes[s:s + m], True
        tb, ln, h, cnt, drops = _replay_block(
            cl, q, tb, ln, jnp.asarray(kk), jnp.asarray(ww), jnp.asarray(on))
        hits.append(np.asarray(h)[:m])
        total += np.asarray(cnt, np.int64)
        dropped += int(drops)
    counts = dict(zip(COUNTERS, (int(x) for x in total)))
    counts["route_drops"] = dropped
    return tb, ln, (np.concatenate(hits) if hits else np.zeros(0, np.int32)), \
        counts
