"""One Ditto memory node on one chip, served through ``repro.core.execute``.

Set-up builds the pool with the program's ``make`` at the fill width,
fills it with one ``execute`` call of SETs, gives every slot the record
of its key (``bench/records.py``), and hands the filled pool to the
cell's client lanes with fresh lane state and counters, as a restarted
client tier would.  Then ``bench/loop.py`` serves the warm-up and the
window.  After the window the program's account, its records classed on
the device, is read back and freed, and ``bench/reference.py`` replays
the same fill and requests from the seed.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import loop
from bench import records
from bench import reference as ref
from bench import traffic as traffic_mod


def pool_of(config: dict) -> ref.Pool:
    p = config["pool"]
    return ref.Pool(n_buckets=p["n_buckets"], assoc=p["assoc"],
                    capacity=p["capacity"], experts=tuple(p["experts"]))


def program_config(config: dict):
    from repro.core import CacheConfig
    p = config["pool"]
    return CacheConfig(n_buckets=p["n_buckets"], assoc=p["assoc"],
                       capacity=p["capacity"], experts=tuple(p["experts"]),
                       value_words=p["value_words"])


def traffic(job):
    """The run's fill and trace, the seeds of the two sets of lanes and the
    salt of the records."""
    lanes = job.config["lanes"]
    tr = traffic_mod.build(job.traffic, nodes=1,
                           capacity=job.config["pool"]["capacity"],
                           lanes=lanes, fill_lanes=job.config["fill_lanes"],
                           seconds=job.seconds, seed=job.seed)
    fill_seed, lane_seed, salt = traffic_mod.seeds(job.seed, 5)[2:]
    return tr, (fill_seed, lane_seed), salt


def run(job, t_start: float, program=None) -> loop.Outcome:
    """One run of the cell.  ``program`` stands in for ``execute`` (the
    fault tests break the timed path through it)."""
    from repro.core import execute as repro_execute
    from repro.core import make
    from repro.core.execute import Cache
    from repro.core.types import init_clients, init_stats

    execute = program or repro_execute
    cfg = program_config(job.config)
    clock = loop.Phases()
    tr, (fill_seed, lane_seed), salt = traffic(job)
    salt = records.salt(salt)
    clock.done(f"traffic ({tr.keys.size} requests)")
    fill = execute(make(cfg, job.config["fill_lanes"], seed=fill_seed),
                   tr.fill_keys, is_write=tr.fill_keys != 0, plan=None)
    fill_hits = np.asarray(fill.hits)
    state = fill.state
    del fill
    state = state._replace(values=records.write(state.values, state.key,
                                                salt))
    clock.done(f"fill ({tr.fill_keys.shape[0]} rounds) and records")
    cache = Cache(cfg, state,
                  init_clients(cfg, job.config["lanes"], lane_seed),
                  init_stats())
    del state
    cache, out, lane_hits, rows = loop.serve(job, t_start, cache, execute, tr)
    got = dict(loop.read_back(cache), fill_hits=fill_hits,
               lane_hits=lane_hits,
               record_class=np.asarray(records.classes(
                   cache.state.values, cache.state.key, salt)))
    del cache
    clock = loop.Phases()
    want = replay(job.config, tr, fill_seed, lane_seed, rows)
    clock.done(f"reference ({rows} rounds after the fill)")
    out.checks = loop.compare(got, want, rows * tr.keys.shape[1],
                              job.config["limits"])
    return out


def replay(config: dict, tr, fill_seed: int, lane_seed: int, rows: int,
           control: bool = False) -> dict:
    """The reference's account of the same run: the fill at the fill
    width, then the first ``rows`` rounds at the cell's lanes.  With
    ``control`` it is computed a precision narrower (the control)."""
    pool, wdt = ref.precision(pool_of(config), control)
    tb = ref.empty_table(pool, wdt)
    ln = ref.new_lanes(pool, config["fill_lanes"], fill_seed, wdt)
    tb, ln, fill_hits, _ = ref.replay(pool, tb, ln, tr.fill_keys,
                                      tr.fill_keys != 0)
    # set-up writes every slot's record after the fill
    tb = tb._replace(written=jnp.zeros_like(tb.written))
    del ln
    ln = ref.new_lanes(pool, config["lanes"], lane_seed, wdt)
    tb, ln, lane_hits, counts = ref.replay(pool, tb, ln, tr.keys[:rows],
                                           tr.writes[:rows])
    out = {c: np.asarray(tb.column(c)) for c in loop.COLUMNS}
    out.update({c: np.asarray(getattr(tb, c)) for c in loop.SCALARS})
    out.update(weights=np.asarray(tb.weights, np.float32),
               local_w=np.asarray(ln.local_w, np.float32),
               fill_hits=fill_hits, lane_hits=lane_hits,
               record_class=records.expected(tb.written),
               counters=dict(counts, route_drops=0))
    return out
