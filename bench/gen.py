"""YCSB traffic, copied from the program's ``workloads/gen.py`` so that the
benchmark's traffic cannot move with the program.

``zipfian`` and ``ycsb`` keep the original's semantics and random streams:
for a seed they return the same keys and write flags as the originals.
Only the zipfian sampler is faster.  The original draws ranks with
``Generator.choice(p=...)``, an inverse-CDF binary search that costs about
0.5 us a request on the host; here the same uniforms go through the same
CDF with a guide table (Chen's method), which returns the same ranks at a
sixth of the cost.  Keys are uint32 >= 1 (0 is the no-op pad).
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=4)
def _zipf_tables(n_keys: int, theta: float):
    """The normalized CDF over ranks and a guide table into it: for each
    of n_keys equal bins of [0, 1), the first rank whose CDF exceeds the
    bin's lower edge.  Cached: building them is O(n_keys)."""
    p = np.arange(1, n_keys + 1, dtype=np.float64) ** (-theta)
    p /= p.sum()
    cdf = p.cumsum()
    cdf /= cdf[-1]
    guide = cdf.searchsorted(np.arange(n_keys) / n_keys, side="right")
    return cdf, guide


def _ranks(rng: np.random.Generator, n: int, n_keys: int,
           theta: float) -> np.ndarray:
    """``rng.choice(n_keys, size=n, p=zipf(theta))``: the same draw of
    uniforms, the same ``searchsorted(cdf, u, side="right")`` ranks."""
    cdf, guide = _zipf_tables(n_keys, theta)
    u = rng.random(n)
    idx = guide[np.minimum((u * n_keys).astype(np.int64), n_keys - 1)]
    act = np.nonzero(cdf[idx] <= u)[0]
    while act.size:
        idx[act] += 1
        act = act[cdf[idx[act]] <= u[act]]
    return np.minimum(idx, n_keys - 1)


def zipfian(n_requests: int, n_keys: int, theta: float = 0.99,
            seed: int = 0, scramble: bool = True) -> np.ndarray:
    """YCSB-style (scrambled) zipfian key stream."""
    rng = np.random.default_rng(seed)
    ranks = _ranks(rng, n_requests, n_keys, theta)
    if scramble:
        perm = rng.permutation(n_keys)
        ranks = perm[ranks]
    return (ranks + 1).astype(np.uint32)


def ycsb(workload: str, n_requests: int, n_keys: int = 100_000,
         theta: float = 0.99, seed: int = 0):
    """YCSB core workloads A-C. Returns (keys u32[N], is_write bool[N])."""
    rng = np.random.default_rng(seed + 17)
    keys = zipfian(n_requests, n_keys, theta, seed)
    w = workload.upper()
    if w == "A":
        is_write = rng.random(n_requests) < 0.5
    elif w == "B":
        is_write = rng.random(n_requests) < 0.05
    elif w == "C":
        is_write = np.zeros(n_requests, bool)
    else:
        raise ValueError(f"unknown YCSB workload {workload!r}")
    return keys, is_write
