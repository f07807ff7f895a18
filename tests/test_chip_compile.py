"""Compile guards for the chip: the main path's kernels and jitted steps,
compiled by the installed TPU compiler for a described (not attached)
v5e chip at the sizes ``chip_smoke.py`` runs. Nothing executes; a compile
that the chip's compiler would refuse (a block not aligned to the tiling,
too much scoped VMEM, a kernel that cannot be partitioned) fails here.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test runner's workers import every test file."""
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import backend as be
from repro.core.backend import FOLD_BLOCK, _make_mesh_fold
from repro.core.cache import PAYLOAD_WIDTH
from repro.core.transformer import transform_rollup_kernel
from repro.kernels.hash_join.hash_join import hash_join_kernel
from repro.kernels.segment_kpi.segment_kpi import (fold_segments_kernel,
                                                   gather_stats_kernel,
                                                   segment_kpi_kernel,
                                                   segment_rollup_kernel)
from repro.serving.views import steelworks_views

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

STEP = smoke.STEP_RECORDS
VIEWS = {s.name: (s.n_segments, s.n_lanes)
         for s in steelworks_views(smoke.N_UNITS)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel lowered without Mosaic"
    return text


@pytest.mark.parametrize("n_slots", [4096, smoke.CACHE_SLOTS])
def test_hash_join_compiles(one_chip, n_slots):
    _compile_kernel(
        functools.partial(hash_join_kernel, block_q=256, interpret=False),
        one_chip((STEP,), jnp.int32), one_chip((n_slots,), jnp.int32),
        one_chip((n_slots, PAYLOAD_WIDTH)), one_chip((n_slots,), jnp.int32))


def test_segment_kpi_compiles(one_chip):
    rows = one_chip((STEP, 8))
    _compile_kernel(functools.partial(segment_kpi_kernel,
                                      n_units=smoke.N_UNITS, interpret=False),
                    rows, rows, rows)


def test_segment_rollup_compiles(one_chip):
    _compile_kernel(functools.partial(segment_rollup_kernel,
                                      n_units=smoke.N_UNITS, interpret=False),
                    one_chip((STEP, 10)))


@pytest.mark.parametrize("view", sorted(VIEWS))
def test_fold_segments_compiles(one_chip, view):
    n_segments, lanes = VIEWS[view]
    _compile_kernel(functools.partial(fold_segments_kernel,
                                      n_segments=n_segments, interpret=False),
                    one_chip((FOLD_BLOCK, 1 + lanes)))


@pytest.mark.parametrize("view", sorted(VIEWS))
def test_gather_stats_compiles(one_chip, view):
    n_segments, lanes = VIEWS[view]
    _compile_kernel(functools.partial(gather_stats_kernel, interpret=False),
                    one_chip((n_segments, 1 + 3 * lanes)),
                    one_chip((4096, 1)))


def test_transform_rollup_compiles(one_chip):
    slots = smoke.CACHE_SLOTS
    table = (one_chip((slots,), jnp.int32), one_chip((slots, PAYLOAD_WIDTH)),
             one_chip((slots,), jnp.int32))
    transform_rollup_kernel.lower(one_chip((STEP, 8)), *table, *table,
                                  n_units=smoke.N_UNITS).compile()


@pytest.mark.parametrize("block", [128, 1024])
def test_dimtrade_transform_compiles(one_chip, block):
    """The DimTrade kernel at the tpcdi.trade-backlog cell's DimAccount
    slots per worker (2**25, 8 int32 lanes) and DimSecurity slots."""
    from repro.core.dimtrade import ACCOUNT_COLUMNS, dimtrade_transform_kernel

    def table(slots):
        return (one_chip((slots,), jnp.int32),
                one_chip((slots, 8), jnp.int32),
                one_chip((slots,), jnp.int32))
    compiled = dimtrade_transform_kernel.lower(
        one_chip((block, 16), jnp.int32), *table(1 << 25), *table(1 << 18),
        asof_lane=ACCOUNT_COLUMNS.index("effective_date")).compile()
    mem = compiled.memory_analysis()
    if mem is not None:
        assert mem.temp_size_in_bytes < (1 << 30)


@pytest.mark.parametrize("view", sorted(VIEWS))
def test_fold_tree_and_gather_compile(one_chip, view):
    n_segments, lanes = VIEWS[view]
    # the jitted twins are built on first use: build them on the CPU
    be._fold_tree_jnp(jnp.zeros(8, jnp.int32), jnp.zeros((8, lanes)), 1)
    be._gather_stats_jnp(jnp.zeros((1, 1 + 3 * lanes)),
                         jnp.zeros(1, jnp.int32))
    be._FOLD_JIT.lower(one_chip((FOLD_BLOCK,), jnp.int32),
                       one_chip((FOLD_BLOCK, lanes)),
                       n_segments=n_segments).compile()
    be._GATHER_JIT.lower(one_chip((n_segments, 1 + 3 * lanes)),
                         one_chip((4096,), jnp.int32)).compile()


def test_shard_map_fold_compiles_on_four_devices(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("shards",))
    assert mesh.devices.size == 4
    rep = NamedSharding(mesh, PartitionSpec())
    n_segments, lanes = VIEWS["kpi_by_unit_shift"]
    compiled = _make_mesh_fold(mesh).lower(
        jax.ShapeDtypeStruct((FOLD_BLOCK,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((FOLD_BLOCK, lanes), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((n_segments,), jnp.int32, sharding=rep),
        n_segments=n_segments).compile()
    out = compiled.output_shardings
    assert out.spec == PartitionSpec("shards")
    # the write path is device-local: no collective crosses the mesh
    text = compiled.as_text()
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute"):
        assert op not in text, op
