"""64-bit keys through RAMS: u64, f64 and i64, which psort's key transform
maps onto u64 and RAMS classifies as (hi, lo, tie) u32 planes.

Every answer is compared with ``np.sort`` (``helpers.check_sort``: exact
keys, overflow 0, the permutation a bijection) on the shard_map backend at
p = 4 and 8 and on the sim backend at p = 64 with two levels.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm
from repro.core.api import (SortConfig, _device_program, _plan, _sim_runner,
                            default_mesh, psort)
from repro.core.selection import select_algorithm
from repro.core.types import local_kernels
from repro.data.distributions import INSTANCES, generate_instance
from helpers import check_sort

# (p, extra SortConfig fields, n): one compiled program per row
BACKENDS = {
    "shard_map-p4": (4, {}, 4096),
    "shard_map-p8": (8, {}, 4096),
    "sim-p64-two-levels": (64, {"backend": "sim", "levels": 2}, 64 * 64),
}
_GOLD = np.uint64(0x9E3779B9)
_LO = np.uint64(0xFFFFFFFF)


def spread(x, how: str) -> np.ndarray:
    """Values in [0, 2^32) of an instance over both words of a u64 key;
    equal values stay equal keys, so duplicate instances stay duplicate.

    ``both``: distinct values differ in both words; ``hi_equal``: many keys
    share the high word and the low word decides; ``lo_equal``: every key
    has the same low word and the high word decides."""
    x = np.asarray(x).astype(np.uint64)
    if how == "both":
        return (x << np.uint64(32)) | ((x * _GOLD) & _LO)
    if how == "hi_equal":
        return ((x >> np.uint64(24)) << np.uint64(32)) | x
    if how == "lo_equal":
        return (x << np.uint64(32)) | np.uint64(7)
    raise ValueError(how)


def wide_keys(dtype: str, n: int, seed: int = 0) -> np.ndarray:
    r = np.random.default_rng(seed)
    if dtype == "uint64":
        x = r.integers(0, 2**64 - 1, size=n, dtype=np.uint64, endpoint=True)
        x[:4] = [0, 2**64 - 1, 2**32, 2**32 - 1]
        return x
    if dtype == "int64":
        x = r.integers(-2**63, 2**63 - 1, size=n, dtype=np.int64,
                       endpoint=True)
        x[:5] = [-2**63, 2**63 - 1, -1, 0, 1]
        return x
    x = r.normal(size=n) * np.exp(r.uniform(-30, 30, size=n))
    x[:8] = [-0.0, 0.0, np.inf, -np.inf, 5e-324, -5e-324, -1.0, 1.0]
    x[8:n // 4] = np.round(x[8:n // 4])      # repeated values, ±0.0 among them
    return x


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", ["uint64", "float64", "int64"])
def test_rams_sorts_64_bit_dtypes(dtype, backend):
    p, kw, n = BACKENDS[backend]
    x = wide_keys(dtype, n)
    info = check_sort(x, p, "rams", check_balance=True, **kw)
    assert info["algorithm"] == "rams"


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("how", ["both", "hi_equal", "lo_equal"])
@pytest.mark.parametrize("instance", sorted(INSTANCES))
def test_rams_sorts_every_instance_in_64_bits(instance, how, backend):
    p, kw, n = BACKENDS[backend]
    x = spread(generate_instance(instance, p, n, seed=2), how)
    check_sort(x, p, "rams", check_balance=True, **kw)


@pytest.mark.parametrize("algorithm", ["rams", "ntb-ams"])
def test_streamed_exchange_is_bitwise_the_barrier_one_on_u64(algorithm):
    x = wide_keys("uint64", 4096, seed=3)
    barrier = check_sort(x, 8, algorithm)
    streamed = check_sort(x, 8, algorithm, overlap=True)
    np.testing.assert_array_equal(barrier["perm"], streamed["perm"])


@pytest.mark.parametrize("dtype", ["uint64", "float64", "int64"])
def test_default_four_chip_call_sorts_2_20_wide_keys(dtype):
    """The default call on a four-device mesh picks RAMS at n = 2^20 and
    sorts 64-bit keys exactly."""
    n = 1 << 20
    assert select_algorithm(n, 4) == "rams"
    x = wide_keys(dtype, n, seed=5)
    out, info = psort(x, config=SortConfig(mesh=default_mesh(4)),
                      return_info=True)
    assert info["algorithm"] == "rams" and info["overflow"] == 0
    out = np.asarray(out)
    assert out.dtype == x.dtype
    np.testing.assert_array_equal(out, np.sort(x))
    assert np.array_equal(np.sort(info["perm"]), np.arange(n))


# Collective volume of one u64 RAMS sort at p = 4, n = 2^20 (per PE, in
# bytes).  psort provisions cap = 2 * 2^18 = 2^19 slots a PE.
#   shuffle: slot_cap = ceil(2 * 2^17 + 6 * sqrt(2^17) + 6) = 264323; one
#     all_to_all of 4 * 264323 slots of an 8-byte key and a 4-byte index,
#     and 4 int32 counts.
#   level0 (the one level at p = 4; cap 2^20 after the resize, nb = 16):
#     all_gather of 16 samples of three u32 planes; two ppermutes of the
#     16-bucket int64 histogram (the subcube scan); slot_cap = ceil(2 *
#     2^18 + 6 * 2^9 + 6) = 527366, one all_to_all of 4 * 527366 slots of
#     key and index, and 4 int32 counts.
SHUFFLE_BYTES = 4 * 264323 * (8 + 4) + 4 * 4
LEVEL0_BYTES = 16 * 3 * 4 + 2 * 16 * 8 + 4 * 527366 * (8 + 4) + 4 * 4
WIDE_WIRE_BYTES = SHUFFLE_BYTES + LEVEL0_BYTES            # 38,001,552


def _counted(dtype, n=1 << 20, p=4):
    plan = _plan((n,), SortConfig(p=p, algorithm="rams", backend="sim"))
    assert (plan.per, plan.capacity) == (n // p, 2 * (n // p))
    counter = comm.CountingCollectives(comm.SIM)
    jax.eval_shape(_sim_runner(plan, impl=counter),
                   jax.ShapeDtypeStruct((p, plan.per), dtype),
                   jax.ShapeDtypeStruct((p,), jnp.int32))
    return counter.trace


def test_u64_sort_collectives_counted_per_phase():
    trace = _counted(jnp.uint64)
    assert trace.tags() == ["level0", "shuffle"]
    assert trace.filter(tag="shuffle").wire_bytes() == SHUFFLE_BYTES
    assert trace.filter(tag="level0").wire_bytes() == LEVEL0_BYTES
    assert trace.wire_bytes() == WIDE_WIRE_BYTES == 38_001_552
    assert trace.counts() == {"all_to_all": 6, "all_gather": 1,
                              "ppermute": 2}
    # the 8-byte key plane is the whole difference from a u32 sort, bar
    # the samples' wider rows (three u32 planes against one u64 word)
    narrow = _counted(jnp.uint32)
    assert trace.counts() == narrow.counts()
    assert trace.wire_bytes() - narrow.wire_bytes() == \
        4 * (4 * 264323 + 4 * 527366) + 16 * (12 - 8)


def test_u64_device_program_names_its_splitter_pick():
    plan = _plan((4 * 256,), SortConfig(mesh=default_mesh(4),
                                        algorithm="rams"))
    keys = jnp.zeros(plan.lead + (plan.per,), jnp.uint64)
    counts = jnp.full(plan.lead, plan.per, jnp.int32)
    text = _device_program.lower(keys, counts, plan=plan,
                                 pallas=local_kernels()).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any("level0/splitters/" in name and "sort" in name
               for name in names), sorted(names)[:20]
