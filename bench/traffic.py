"""The one generator every traffic mix goes through.

A mix is a file ``bench/traffic/<name>.json`` of parameters:

- ``ycsb``: the YCSB core workload letter (``A``, ``B`` or ``C``);
- ``theta``: the zipfian constant;
- ``keys_per_node``: the key space, per memory node of the configuration;
- ``fill``: how set-up fills the pool before the window, either
  ``{"distinct_sets_per_capacity": x}`` (that many distinct keys, SET once
  each) or ``{"every_key": true}`` (every key of the key space, SET once);
- ``rounds_per_call``: rounds (one request per lane) in each call;
- ``warmup_calls``: calls made in set-up, compiles included;
- ``max_ops_per_s_per_node``: the most the trace can feed: the trace holds
  that rate times the window, and a run that would need more fails;
- ``hit_rate_ops_per_node``: the fixed number of operations from the start
  of the window over which ``hit_rate`` is counted;
- ``trace_calls``: calls traced at the start of a ``--trace 1`` window.

Everything here follows from the seed: the same seed gives the same fill
and the same requests.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from bench.gen import ycsb


@dataclass
class Traffic:
    fill_keys: np.ndarray      # u32[R_fill, fill_lanes]
    keys: np.ndarray           # u32[R, lanes]
    writes: np.ndarray         # bool[R, lanes]
    rounds_per_call: int
    warmup_calls: int
    hit_rate_ops: int
    trace_calls: int
    n_keys: int


def seeds(seed: int, n: int) -> list:
    """n independent 31-bit seeds derived from the run's seed (which may
    exceed 32 bits)."""
    ss = np.random.SeedSequence(int(seed))
    return [int(x) for x in ss.generate_state(n, np.uint32) >> 1]


def build(mix: dict, *, nodes: int, capacity: int, lanes: int,
          fill_lanes: int, seconds: float, seed: int) -> Traffic:
    """The fill and the request trace of one run.  ``capacity`` is the
    whole pool's; ``lanes`` is the total request width of one round."""
    s_fill, s_trace = seeds(seed, 2)
    n_keys = int(mix["keys_per_node"]) * nodes
    fill = mix["fill"]
    rng = np.random.default_rng(s_fill)
    if fill.get("every_key"):
        fill_keys = rng.permutation(n_keys) + 1
    else:
        n_fill = int(round(fill["distinct_sets_per_capacity"] * capacity))
        if n_fill > n_keys:
            raise ValueError(f"fill of {n_fill} distinct keys exceeds the "
                             f"key space of {n_keys}")
        fill_keys = rng.permutation(n_keys)[:n_fill] + 1
    pad = -len(fill_keys) % fill_lanes
    fill_keys = np.concatenate([fill_keys, np.zeros(pad, np.int64)])
    rpc = int(mix["rounds_per_call"])
    per_call = rpc * lanes
    window_calls = math.ceil(float(mix["max_ops_per_s_per_node"]) * nodes
                             * seconds / per_call)
    n_calls = int(mix["warmup_calls"]) + window_calls
    keys, writes = ycsb(mix["ycsb"], n_calls * per_call, n_keys=n_keys,
                        theta=float(mix["theta"]), seed=s_trace)
    return Traffic(
        fill_keys=fill_keys.astype(np.uint32).reshape(-1, fill_lanes),
        keys=keys.reshape(-1, lanes), writes=writes.reshape(-1, lanes),
        rounds_per_call=rpc, warmup_calls=int(mix["warmup_calls"]),
        hit_rate_ops=int(mix["hit_rate_ops_per_node"]) * nodes,
        trace_calls=int(mix["trace_calls"]), n_keys=n_keys)
