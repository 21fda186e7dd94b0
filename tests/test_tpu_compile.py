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


@pytest.fixture(scope="module")
def compiled_runner(one_chip):
    """The sequential trace program execute() runs, compiled (not
    interpreted) with donated state for 64 rounds, once per (backend,
    config, lanes) in this module."""
    done = {}

    def compile_(backend, cfg, lanes):
        if (backend, cfg, lanes) not in done:
            run_cfg = merge_exec_config(cfg, ExecConfig(backend=backend))
            fn = _runner(run_cfg, False, True, False)
            done[backend, cfg, lanes] = fn.lower(
                *_runner_args(cfg, lanes, 64, one_chip)).compile()
        return done[backend, cfg, lanes]
    return compile_


@pytest.mark.parametrize("backend,cfg,lanes", [
    ("reference", SMOKE, 256),                       # the smoke's pool
    ("fused", CacheConfig(n_buckets=FUSED_MAX_SLOTS // 8, assoc=8,
                          capacity=2**13, experts=("lru", "lfu")), 64),
])
def test_trace_runner_compiles(compiled_runner, backend, cfg, lanes):
    """The whole sequential trace program execute() runs, compiled at the
    chip smoke's sizes; the access round's stage scopes survive into its
    op metadata."""
    compiled = compiled_runner(backend, cfg, lanes)
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (backend == "fused")
    scopes = {c for n in re.findall(r'op_name="([^"]*)"', text)
              for c in n.split("/")}
    for stage in ("probe", "hit_update", "evict", "apply", "account"):
        assert f"ditto.{stage}" in scopes, stage
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16 * 2**30


# "%scatter.3 = u32[8388608]{0:T(1024)} scatter(%p.1, %t.2, %t.3), ...":
# name, type, opcode, operands
_HLO_INSTR = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ([\w\-]+)\(([^)]*)\)")


def _hlo_instrs(text):
    """(name, type, opcode, operand names, op_name) of every instruction
    of a compiled module, fused computations included."""
    out = []
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m:
            meta = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), re.sub(r"\{.*", "", m.group(2)),
                        m.group(3), re.findall(r"%[\w.\-]+", m.group(4)),
                        meta.group(1) if meta else ""))
    return out


def test_smoke_runner_victim_path_has_no_pool_sized_work(compiled_runner):
    """At 256 lanes the round's 1,280 sampled victims and 256 bucket
    inserts are deduplicated without a table the size of the pool or of
    the bucket array, the eviction write-back scatters only chunks of the
    live victims, and no pool column is copied more often than before:
    XLA:TPU scatters cost time per update index, dropped ones included."""
    lanes = 256
    n_slots = SMOKE.n_buckets * SMOKE.assoc
    victims = lanes * SMOKE.n_samples
    text = compiled_runner("reference", SMOKE, lanes).as_text()
    instrs = _hlo_instrs(text)
    types = {name: ty for name, ty, *_ in instrs}
    tables = (f"[{n_slots + 1}]", f"[{SMOKE.n_buckets + 1}]")
    staged = [i for i in instrs
              if {"ditto.evict", "ditto.apply"} & set(i[4].split("/"))]
    assert any(op == "scatter" for _, _, op, _, _ in staged)
    for name, ty, op, args, _ in staged:
        assert not ty.endswith(tables), (name, ty)
        if op == "scatter":
            n_idx = int(re.match(r"\w+\[(\d+)", types[args[1]]).group(1))
            assert n_idx < victims, (name, types[args[1]])
    # Copies of a pool column of every kind, synchronous or async.  The
    # runner before the victim compaction held three: async moves of a
    # written column between memory spaces at the end of the scan body.
    col_copy = re.compile(
        rf"\s*(?:ROOT )?%[\w.\-]+ = \(?u32\[{n_slots}\][^=]*? (copy|copy-start)\(")
    copies = [line.strip() for line in text.splitlines() if col_copy.match(line)]
    assert len(copies) <= 3, copies


def test_dm_execute_compiles_on_four_chips(topo):
    """The pipelined DM scan over a described 2x2 mesh at the four-chip
    smoke's pool (one smoke pool per chip): the router's all-to-all is
    in the program."""
    from repro.dm.sharded_cache import (AXIS, _dm_init, dm_execute,
                                        identity_membership)
    S, lanes = 4, 64
    cfg = dataclasses.replace(SMOKE, n_buckets=S * SMOKE.n_buckets,
                              capacity=S * SMOKE.capacity)
    local = dataclasses.replace(
        cfg, n_buckets=SMOKE.n_buckets, capacity=SMOKE.capacity,
        hist_len=cfg.history_len // S)
    mesh = Mesh(np.array(topo.devices[:S]), (AXIS,))

    def build():
        return _dm_init(cfg, local, S, lanes, 0)

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


def test_four_chip_cluster_holds_one_shard_a_chip(topo):
    """The four-chip benchmark's cluster (four ditto-1chip nodes, 1 KB
    records, 256 lanes a shard) compiled for a described 2x2 mesh: the
    construction makes each shard on its own chip and no chip more than
    one shard's state; the runner, its pool donated, holds one copy of a
    shard and little else."""
    from repro.dm.cluster import runner
    from repro.dm.sharded_cache import (AXIS, _dm_init, _local_config,
                                        identity_membership)
    S, lanes = 4, 256
    cfg = dataclasses.replace(SMOKE, n_buckets=S * SMOKE.n_buckets,
                              capacity=S * SMOKE.capacity, value_words=256)
    local = _local_config(cfg, S)
    mesh = Mesh(np.array(topo.devices[:S]), (AXIS,))
    sharded = NamedSharding(mesh, P(AXIS))
    build = jax.jit(lambda: _dm_init(cfg, local, S, lanes, 0),
                    out_shardings=sharded)
    made = build.lower().compile().memory_analysis()
    total = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves(jax.eval_shape(build)))
    assert made.output_size_in_bytes <= total // S + 2**20
    assert made.temp_size_in_bytes <= 2**20
    assert made.output_size_in_bytes < 9.2e9

    whole = NamedSharding(mesh, P())
    spec = lambda tree, sh: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), tree)
    dm = spec(jax.eval_shape(build), sharded)
    member = spec(jax.eval_shape(
        lambda: identity_membership(S, cfg.n_buckets)), whole)
    req = [jax.ShapeDtypeStruct((8, S * lanes), dt,
                                sharding=NamedSharding(mesh, P(None, AXIS)))
           for dt in (jnp.uint32, jnp.bool_)]
    fn = runner(mesh, local, 4, True)
    mem = fn.lower(dm, *req, None, None, member=member).compile(
        ).memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes >= made.output_size_in_bytes - 2**20
    assert held < 9.5e9, held
