"""The comparison that decides ``correct`` fails what it has to fail.

The control (the reference one key precision lower) and each fault a cell
can have, planted in the program underneath ``psort``, drive a whole run at
a small size on the CPU and must come out as not correct.  The same control
runs on the chip at the cells' own sizes with ``bench/run.py --control 1``.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401  (turns on the 64-bit types psort uses)
from bench import check, run

PEAKS = {"hbm_bytes_per_s": 819e9}


def small_cell(name, n=1 << 12):
    cell = run.load_cell(name)
    cell.traffic = dict(cell.traffic, n=n)
    return cell


def drive(name, chips, sort=None, n=1 << 12):
    jax.clear_caches()
    try:
        return run.run_cell(small_cell(name, n), 2**31 + 17, 0.2, False,
                            jax.devices()[:chips], PEAKS, sort=sort)
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("dtype", [np.uint32, np.uint64])
def test_device_and_host_digests_agree(dtype):
    x = np.random.default_rng(3).integers(0, np.iinfo(dtype).max, 5000,
                                          dtype=dtype, endpoint=True)
    dev = np.asarray(check.device_digest_fn()(jnp.asarray(x)))
    assert np.array_equal(dev, check.host_digest(x))
    for bit in (0, 8 * x.itemsize - 1):
        y = x.copy()
        y[17] ^= dtype(1) << dtype(bit)
        assert not np.array_equal(check.host_digest(y),
                                  check.host_digest(x))
    swapped = x.copy()
    swapped[[3, 4]] = swapped[[4, 3]]
    assert not np.array_equal(check.host_digest(swapped),
                              check.host_digest(x))


@pytest.mark.parametrize("dtype", [np.uint32, np.uint64])
def test_control_orders_by_all_but_the_low_bits(dtype):
    # 2^20 keys: as at the cells' sizes, some share all but their low bits
    x = np.random.default_rng(5).integers(0, np.iinfo(dtype).max, 1 << 20,
                                          dtype=dtype, endpoint=True)
    got = check.control_sort(x)
    assert np.array_equal(np.sort(got), np.sort(x))
    drop = check.CONTROL_DROP_BITS[x.itemsize]
    assert np.all(np.diff(got >> dtype(drop)) >= 0)
    assert not np.array_equal(got, np.sort(x))


def test_judge_counts():
    x = np.random.default_rng(4).integers(0, 2**32, 1000, dtype=np.uint32)
    good = check.host_digest(np.sort(x))
    bad = np.sort(x)
    bad[5] += 1
    checks = check.judge([x], [0, 0, 0], [good, None, good], bad, 0, 1000)
    assert checks == {"wrong_calls": (1, 0), "wrong_keys_last": (1, 0),
                      "calls_checked": (3, 1)}
    assert not check.passed(checks)
    assert check.passed(check.judge([x], [0], [good], np.sort(x), 0, 1000))


@pytest.mark.parametrize("name,chips", [("v5e1.uniform.lg24", 1),
                                        ("v5e4.uniform.lg20", 4)])
def test_control_is_not_correct(name, chips):
    dev = jax.devices()[0]
    # 2^16 keys, so that keys share their top 24 bits in every input, as
    # they do at the cells' sizes
    result, checks = drive(
        name, chips,
        sort=lambda keys: jax.device_put(check.control_sort(keys), dev),
        n=1 << 16)
    assert not result["correct"]
    assert checks["wrong_calls"][0] == result["attempted"]
    assert checks["wrong_keys_last"][0] > 0


def _unchanged(monkeypatch):
    # every local sort returns its shard as it came: at p = 1 psort's only
    # work (make_shard's and rquick's local sorts)
    for mod in ("repro.core.types", "repro.core.rquick"):
        monkeypatch.setattr(importlib.import_module(mod), "local_sort",
                            lambda shard: shard)


def _half(monkeypatch):
    import repro.core.api as api
    real = api.uint_to_key
    monkeypatch.setattr(api, "uint_to_key",
                        lambda u, dt: real(u[: u.shape[-1] // 2], dt))


def _no_exchange(monkeypatch):
    # each chip keeps what it would send: RAMS' all-to-all and the
    # pairwise exchanges of rquick, which the small test size selects
    from repro.core.comm import LaxCollectives
    for name in ("all_to_all", "ppermute"):
        monkeypatch.setattr(LaxCollectives, name,
                            lambda self, x, *a, **k: x)


def _altered(monkeypatch):
    import repro.core.api as api
    real = api.uint_to_key
    monkeypatch.setattr(api, "uint_to_key",
                        lambda u, dt: real(u.at[7].set(u[7] ^ 1), dt))


@pytest.mark.parametrize("fault,name,chips", [
    (_unchanged, "v5e1.uniform.lg24", 1),
    (_half, "v5e1.uniform.lg24", 1),
    (_half, "v5e4.uniform.lg20", 4),
    (_no_exchange, "v5e4.uniform.lg20", 4),
    (_altered, "v5e1.uniform.lg24", 1),
    (_altered, "v5e4.uniform.lg20", 4),
], ids=["unchanged-1", "half-1", "half-4", "no_exchange-4", "altered-1",
        "altered-4"])
def test_a_fault_underneath_psort_is_not_correct(monkeypatch, fault, name,
                                                 chips):
    fault(monkeypatch)
    result, checks = drive(name, chips)
    assert not result["correct"]
    assert result["failed"] == result["attempted"] >= run.MIN_CALLS
    assert checks["wrong_keys_last"][0] > 0
