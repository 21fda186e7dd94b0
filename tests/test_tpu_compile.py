"""Compile the main path for a described TPU v5e (no chip attached).

The TPU compilers are installed here and compile for a chip that is only
described, so what Mosaic or XLA:TPU would refuse on the chip — a
misaligned block, a primitive Mosaic cannot lower, more VMEM than a core
has — fails here, at no chip time.  Nothing runs: these tests say
nothing about results or speed.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, Mesh, \
    SingleDeviceSharding

from repro.core import CacheConfig, ExecConfig
from repro.core.execute import _runner
from repro.core.types import (init_cache, init_clients, init_stats,
                              merge_exec_config)
from repro.kernels.bucket_lookup import access_probe
from repro.kernels.metadata_update import hit_metadata_update
from repro.kernels.runtime import FUSED_MAX_SLOTS
from repro.kernels.sampled_eviction import ranked_eviction

B = 512                       # requests per kernel call
SMOKE = CacheConfig(n_buckets=2**20, assoc=8, capacity=2**22,
                    experts=("lru", "lfu"))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_call(name, one_chip):
    n = FUSED_MAX_SLOTS
    col = _spec(one_chip, (n,), jnp.uint32)
    req = lambda dt: _spec(one_chip, (B,), dt)
    if name == "access_probe":
        fn = lambda *a: access_probe(*a, assoc=8, history_len=2**21,
                                     interpret=False)
        return fn, (col, col, col, col, req(jnp.uint32),
                    _spec(one_chip, (), jnp.uint32))
    if name.startswith("ranked_eviction"):
        args = [col] * 4 + [req(jnp.int32), req(jnp.int32), req(jnp.bool_),
                            req(jnp.int32), req(jnp.float32)]
        if name.endswith("tenants"):
            args += [col, req(jnp.int32)]
        fn = lambda *a: ranked_eviction(*a, window=20, k=5,
                                        experts=("lru", "lfu"),
                                        interpret=False)
        return fn, tuple(args)
    fn = lambda *a: hit_metadata_update(*a, interpret=False)
    return fn, (col, col, _spec(one_chip, (n, 4), jnp.float32),
                req(jnp.int32), req(jnp.uint32), req(jnp.int32),
                req(jnp.uint32))


@pytest.mark.parametrize("name", ["access_probe", "ranked_eviction",
                                  "ranked_eviction_tenants",
                                  "hit_metadata_update"])
def test_fused_kernel_compiles_at_cap(one_chip, name):
    """Each production kernel compiles as a Mosaic kernel (not the
    interpreter) for the largest pool the fused backend accepts."""
    fn, args = _kernel_call(name, one_chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_fused_tables_above_cap_exceed_vmem(one_chip):
    """The cap is tight: twice the slots no longer fit a core's VMEM."""
    col = _spec(one_chip, (2 * FUSED_MAX_SLOTS,), jnp.uint32)
    req = lambda dt: _spec(one_chip, (B,), dt)
    fn = lambda *a: ranked_eviction(*a, window=20, k=5, interpret=False)
    with pytest.raises(Exception, match="vmem"):
        jax.jit(fn).lower(col, col, col, col, req(jnp.int32),
                          req(jnp.int32), req(jnp.bool_), req(jnp.int32),
                          req(jnp.float32)).compile()


def _runner_args(cfg, lanes, rows, sharding):
    shape = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)
    trace = [jax.ShapeDtypeStruct((rows, lanes), dt, sharding=sharding)
             for dt in (jnp.uint32, jnp.bool_, jnp.uint32, jnp.uint32)]
    return (shape(jax.eval_shape(lambda: init_cache(cfg))),
            shape(jax.eval_shape(lambda: init_clients(cfg, lanes))),
            shape(jax.eval_shape(init_stats)), *trace)


@pytest.mark.parametrize("backend,cfg,lanes", [
    ("reference", SMOKE, 256),                       # the smoke's pool
    ("fused", CacheConfig(n_buckets=FUSED_MAX_SLOTS // 8, assoc=8,
                          capacity=2**13, experts=("lru", "lfu")), 64),
])
def test_trace_runner_compiles(one_chip, backend, cfg, lanes):
    """The whole sequential trace program execute() runs, compiled (not
    interpreted) with donated state, at the chip smoke's sizes; the
    access round's stage scopes survive into its op metadata."""
    run_cfg = merge_exec_config(cfg, ExecConfig(backend=backend))
    fn = _runner(run_cfg, False, True, False)
    compiled = fn.lower(*_runner_args(cfg, lanes, 64, one_chip)).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (backend == "fused")
    scopes = {c for n in re.findall(r'op_name="([^"]*)"', text)
              for c in n.split("/")}
    for stage in ("probe", "hit_update", "evict", "apply", "account"):
        assert f"ditto.{stage}" in scopes, stage
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16 * 2**30


def test_dm_execute_compiles_on_four_chips(topo):
    """The pipelined DM scan over a described 2x2 mesh at the four-chip
    smoke's pool (one smoke pool per chip): the router's all-to-all is
    in the program."""
    from repro.dm.sharded_cache import (AXIS, DMCache, dm_execute,
                                        identity_membership)
    S, lanes = 4, 64
    cfg = dataclasses.replace(SMOKE, n_buckets=S * SMOKE.n_buckets,
                              capacity=S * SMOKE.capacity)
    local = dataclasses.replace(
        cfg, n_buckets=SMOKE.n_buckets, capacity=SMOKE.capacity,
        hist_len=cfg.history_len // S)
    mesh = Mesh(np.array(topo.devices[:S]), (AXIS,))
    per_shard = lambda x: jnp.broadcast_to(x[None], (S,) + x.shape)

    def build():
        st = init_cache(cfg)
        st = st._replace(**{f: per_shard(getattr(st, f)) for f in (
            "n_cached", "bytes_cached", "hist_ctr", "clock", "weights",
            "gds_L", "capacity_blocks", "tenant_bytes", "tenant_budget",
            "l0_epoch")})
        stats = jax.tree.map(lambda x: jnp.zeros((S,), x.dtype),
                             init_stats())
        return DMCache(st, init_clients(cfg, S * lanes), stats)

    sharded = NamedSharding(mesh, P(AXIS))
    whole = NamedSharding(mesh, P())
    spec = lambda tree, sh: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), tree)
    dm = spec(jax.eval_shape(build), sharded)
    member = spec(jax.eval_shape(
        lambda: identity_membership(S, cfg.n_buckets)), whole)
    keys = jax.ShapeDtypeStruct((8, S * lanes), jnp.uint32, sharding=whole)
    writes = jax.ShapeDtypeStruct((8, S * lanes), jnp.bool_, sharding=whole)
    compiled = jax.jit(
        lambda d, k, w, m: dm_execute(mesh, local, d, k, w, member=m)).lower(
            dm, keys, writes, member).compile()
    assert "all-to-all" in compiled.as_text()
