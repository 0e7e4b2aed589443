"""``local_sort``'s XLA path is the stable sort, bitwise.

With the sort kernel off, ``local_sort`` is one stable ``lax.sort`` of
(keys, *payloads): it must give exactly what NumPy's stable argsort and a
take of every plane give, the invalid tail (keys forced to the pad word,
payloads as they were) included.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401  — flips jax_enable_x64 on
from repro.core.types import (KERNEL_SORT_MAX_WORDS, LocalKernelPolicy,
                              SortShard, _pallas_sortable, local_sort,
                              make_shard, pad_value, set_local_kernels,
                              uint_to_key)

CAP = 1000
KEY_DTYPES = ["uint32", "int32", "float32", "uint64"]
DATA = ["uniform", "duplicates", "pad_word"]


@pytest.fixture
def xla_path():
    prev = set_local_kernels(LocalKernelPolicy())
    yield
    set_local_kernels(prev)


def _words(data, width, rng):
    """Key words in the shard's unsigned type."""
    ut = np.dtype(f"uint{width}")
    top = np.iinfo(ut).max
    if data == "uniform":
        return rng.integers(0, top, CAP, dtype=ut, endpoint=True)
    if data == "duplicates":
        return rng.integers(0, 5, CAP).astype(ut)
    # real keys equal to the pad word, among a few others
    return rng.choice(np.array([0, 7, top - 1, top], ut), CAP)


def _planes(n_planes, rng):
    planes = {"idx": rng.permutation(CAP).astype(np.uint32),
              "w": rng.standard_normal(CAP).astype(np.float32)}
    return dict(list(planes.items())[:n_planes])


@pytest.mark.parametrize("count", [CAP, 613], ids=["full", "partial"])
@pytest.mark.parametrize("data", DATA)
@pytest.mark.parametrize("n_planes", [0, 1, 2])
@pytest.mark.parametrize("key_dtype", KEY_DTYPES)
def test_xla_local_sort_is_the_stable_sort(xla_path, key_dtype, n_planes,
                                           data, count):
    rng = np.random.default_rng([KEY_DTYPES.index(key_dtype), n_planes,
                                 DATA.index(data), count])
    width = np.dtype(key_dtype).itemsize * 8
    words = _words(data, width, rng)
    keys = np.asarray(uint_to_key(jnp.asarray(words), key_dtype))
    planes = _planes(n_planes, rng)

    @jax.jit
    def run(k, vals, c):
        out = local_sort(make_shard(k, count=c, vals=vals, sort_local=False))
        return out.keys, out.vals, out.count

    ks, vs, c = run(jnp.asarray(keys), {k: jnp.asarray(v)
                                        for k, v in planes.items()},
                    np.int32(count))
    pad = pad_value(words.dtype)
    want = np.where(np.arange(CAP) < count, words, pad)
    order = np.argsort(want, kind="stable")
    np.testing.assert_array_equal(np.asarray(ks), want[order])
    assert set(vs) == set(planes)
    for name, v in planes.items():
        np.testing.assert_array_equal(np.asarray(vs[name]), v[order])
    assert int(c) == count


def test_xla_local_sort_carries_row_payload(xla_path):
    """A payload with a row per element rides the sorted position of an
    iota beside the 1-D planes."""
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 9, CAP).astype(np.uint32)
    rows = rng.standard_normal((CAP, 3)).astype(np.float32)
    idx = np.arange(CAP, dtype=np.uint32)
    out = jax.jit(local_sort)(SortShard(
        jnp.asarray(keys), {"rows": jnp.asarray(rows),
                            "idx": jnp.asarray(idx)}, jnp.int32(CAP)))
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(np.asarray(out.keys), keys[order])
    np.testing.assert_array_equal(np.asarray(out.vals["rows"]), rows[order])
    np.testing.assert_array_equal(np.asarray(out.vals["idx"]), order)


_U32 = jnp.uint32


@pytest.mark.parametrize("capacity,key,payloads,kernel", [
    (KERNEL_SORT_MAX_WORDS, _U32, [_U32], True),
    (KERNEL_SORT_MAX_WORDS + 1, _U32, [_U32], False),
    (CAP, _U32, [], True),
    (CAP, jnp.uint64, [_U32], False),       # the kernel sorts 4-byte words
    (CAP, _U32, [_U32, _U32], False),       # and carries one payload
], ids=["at-cap", "above-cap", "keys-only", "u64", "two-payloads"])
def test_kernel_takes_only_small_four_byte_shards(capacity, key, payloads,
                                                  kernel):
    spec = jax.ShapeDtypeStruct((capacity,), key)
    vals = {f"v{i}": jax.ShapeDtypeStruct((capacity,), dt)
            for i, dt in enumerate(payloads)}
    assert _pallas_sortable(SortShard(spec, vals, jnp.int32(0))) == kernel
