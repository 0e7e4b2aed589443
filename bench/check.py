"""The comparison that decides a run's ``correct``.

A cell's guarantee is that ``psort`` returns the input's keys in ascending
order, exactly: ``np.sort`` of the input, position for position.  The
window's answers are arrays of millions of keys, too many to keep, so every
answer is reduced on the device, as soon as it is returned, to a positional
digest of its 32-bit words (a key of 64 bits is two words, low first): two
32-bit sums of a mixed (position, word) pair.  After the window the host
works out the same digest of ``np.sort`` of each input with NumPy alone and
counts the calls whose digest, or length, differs.  The last call's answer
is also pulled whole and compared key by key.  Both counts must be 0.

The control puts in ``psort``'s place the reference computed at the next
lower precision of the key: a 32-bit key ordered by its top 24 bits, a
64-bit key by its top 32.  It breaks the guarantee and has to come out as
not correct.
"""
from __future__ import annotations

import numpy as np

_GOLD = np.uint32(0x9E3779B9)
_SALT = np.uint32(0x85EBCA77)
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
CONTROL_DROP_BITS = {4: 8, 8: 32}     # key bytes -> low bits the control drops


def _fmix(x, xp):
    """murmur3's 32-bit finaliser; ``xp`` is numpy or jax.numpy."""
    x = x ^ (x >> np.uint32(16))
    x = x * _M1
    x = x ^ (x >> np.uint32(13))
    x = x * _M2
    return x ^ (x >> np.uint32(16))


def _words(keys, xp):
    """The keys' 32-bit words, (n * width,) u32; ``keys`` are unsigned."""
    if keys.dtype.itemsize == 4:
        return keys.astype(xp.uint32)
    lo = (keys & xp.uint64(0xFFFFFFFF)).astype(xp.uint32)
    hi = (keys >> xp.uint64(32)).astype(xp.uint32)
    return xp.stack([lo, hi], axis=-1).reshape(-1)


def _digest(keys, xp):
    words = _words(keys, xp)
    pos = xp.arange(words.shape[0], dtype=xp.uint32)
    h1 = xp.sum(_fmix(words + pos * _GOLD, xp), dtype=xp.uint32)
    h2 = xp.sum(_fmix((words ^ _SALT) * _GOLD + pos, xp), dtype=xp.uint32)
    return xp.stack([h1, h2])


def host_digest(keys: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        return _digest(np.asarray(keys), np)


def device_digest_fn():
    """A jitted ``keys (n,) -> (2,) u32`` digest on the device that holds
    the keys; asynchronous like any jitted call.  Its programs are named
    ``jit_bench_digest``, which is how a trace tells them from psort's."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bench_digest(keys):
        return _digest(keys, jnp)

    return bench_digest


def reference(keys: np.ndarray) -> np.ndarray:
    return np.sort(keys)


def control_sort(keys: np.ndarray) -> np.ndarray:
    """The reference one precision lower: keys ordered by all but their
    low :data:`CONTROL_DROP_BITS`, ties in input order."""
    keys = np.asarray(keys)
    drop = CONTROL_DROP_BITS[keys.dtype.itemsize]
    coarse = keys >> keys.dtype.type(drop)
    return keys[np.argsort(coarse, kind="stable")]


def judge(inputs, calls, digests, last_out, last_input_index, n):
    """Counts of what the window got wrong.

    ``calls[i]`` is the input index of call i, ``digests[i]`` its device
    digest (or None when its length was not n), ``last_out`` the last
    call's answer on the host.  Returns {name: (value, limit)}."""
    want = {j: reference(inputs[j]) for j in sorted(set(calls))}
    want_digest = {j: host_digest(s) for j, s in want.items()}
    wrong_calls = sum(
        d is None or not np.array_equal(np.asarray(d, np.uint32),
                                        want_digest[j])
        for j, d in zip(calls, digests))
    ref = want[last_input_index]
    got = np.asarray(last_out)
    m = min(got.size, ref.size)
    wrong_keys = (int(np.count_nonzero(got[:m] != ref[:m]))
                  + abs(int(got.size) - int(ref.size)))
    return {"wrong_calls": (int(wrong_calls), 0),
            "wrong_keys_last": (wrong_keys, 0),
            "calls_checked": (len(calls), 1)}


def passed(checks) -> bool:
    """Every count at most its limit, except ``calls_checked``, which
    must reach it."""
    return all((v >= lim) if name == "calls_checked" else (v <= lim)
               for name, (v, lim) in checks.items())
