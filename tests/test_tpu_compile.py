"""Compile the local kernels for a described TPU v5e, without the chip.

Each case lowers a kernel entry point with ``interpret=False`` (the
override of :func:`repro.kernels.interpret_mode`) against one device of a
described ``v5e:2x2`` topology and asserts that Mosaic produced a kernel
(``tpu_custom_call``).  Interpret-mode tests cannot see what the chip's
compiler refuses (``lax.rev``, ``cumsum``, 64-bit scalars under
``jax_enable_x64``, unaligned blocks); these do.  Nothing runs, so they say
nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  — production traces run under x64
from repro.kernels.bitonic import bitonic
from repro.kernels.bitonic.ops import local_sort_fast
from repro.kernels.kway import kway
from repro.kernels.partition import partition_tile
from repro.kernels.partition.partition import KERNEL_NAME
from repro.kernels.partition.ops import _tile_rows

TILE = 1 << 14              # one bitonic VMEM tile (MAX_TILE)
SHARD = 1 << 18             # keys per PE in the smoke's RAMS phase


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _u32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


@pytest.mark.parametrize("payload", [False, True], ids=["keys", "payload"])
def test_sort_tile_compiles(one_chip, payload):
    args = [_u32(one_chip, TILE)] * (2 if payload else 1)
    txt = _compile_text(lambda *a: bitonic.sort_tile(*a, interpret=False),
                        *args)
    assert "tpu_custom_call" in txt


def test_merge_tiles_compiles(one_chip):
    txt = _compile_text(
        lambda a, b: bitonic.merge_tiles(a, b, interpret=False),
        _u32(one_chip, TILE), _u32(one_chip, TILE))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("nb", [2, 64, 512])
def test_partition_tile_compiles(one_chip, nb):
    R = _tile_rows(nb)
    txt = _compile_text(
        lambda *a: partition_tile(*a, n_buckets=nb, interpret=False),
        _u32(one_chip, R, 128), _u32(one_chip, R, 128),
        _u32(one_chip, nb - 1), _u32(one_chip, nb - 1),
        jax.ShapeDtypeStruct((1, nb + 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("nb", [16, 512])
def test_partition_tile_three_planes_compiles(one_chip, nb):
    # (hi, lo, tie) planes of 64-bit keys; the op carries the kernel's name
    R = _tile_rows(nb)
    tile, spl = _u32(one_chip, R, 128), _u32(one_chip, nb - 1)
    txt = _compile_text(
        lambda hi, lo, t, shi, slo, st, h, nv: partition_tile(
            (hi, lo), t, (shi, slo), st, h, nv, n_buckets=nb,
            interpret=False),
        tile, tile, tile, spl, spl, spl,
        jax.ShapeDtypeStruct((1, nb + 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in txt
    assert f"%{KERNEL_NAME}" in txt


def test_local_sort_fast_compiles_at_rams_shard(one_chip):
    txt = _compile_text(lambda k, v: local_sort_fast(k, v, interpret=False),
                        _u32(one_chip, SHARD), _u32(one_chip, SHARD))
    assert "tpu_custom_call" in txt


def test_kway_classify_compiles(one_chip):
    C, nb = 1 << 16, 128
    txt = _compile_text(
        lambda *a: kway.kway_classify(*a, n_buckets=nb, interpret=False),
        _u32(one_chip, C), _u32(one_chip, C),
        _u32(one_chip, nb - 1), _u32(one_chip, nb - 1))
    assert "tpu_custom_call" in txt
