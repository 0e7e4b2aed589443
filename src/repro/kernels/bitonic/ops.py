"""Jitted public wrappers around the bitonic Pallas kernels.

``local_sort_fast(keys, vals)`` sorts **arbitrary sizes**: non-power-of-two
inputs are padded up to the next power of two with ``pad_val`` and sliced
back after the sort, so real shard capacities take the kernel path.  Inputs
of at most ``MAX_TILE`` words are sorted by one kernel block.  Larger ones
are a full bitonic sort split by distance: one :func:`bitonic.sort_blocks`
launch sorts every ``MAX_TILE`` block; then each of the log2(n/MAX_TILE)
merge rounds is one flip step and the half-cleaner steps at distances of a
block or more as XLA elementwise passes, followed by one
:func:`bitonic.clean_blocks` launch for the distances inside a block.  So
an n-word sort is 1 + log2(n/MAX_TILE) kernel launches.  Only 4-byte words
take the kernel — 64-bit keys fall back to the jnp reference.

Padding caveat (shared with the power-of-two path, whose capacity padding
has the same property): the bitonic network is *not stable*.  ``pad_val``
defaults to the dtype's maximum (+inf for floats) and pads sort to the
back; but when a payload travels along and real keys *equal* the pad
value, a pad entry's payload may be exchanged with a real max-key
element's payload.  Callers that sort max-representable keys with payloads
should pass a ``pad_val`` known to be absent from the data, or use the
stable jnp path (``use_kernel=False``).

On a TPU the kernels compile through Mosaic; elsewhere they run in the
Pallas interpreter (:func:`repro.kernels.interpret_mode`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import bitonic
from .bitonic import LANES

MAX_TILE = 1 << 14          # 16Ki elements/tile: 64 KiB keys + 64 KiB vals


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def supported(n: int, dtype) -> bool:
    """Does ``local_sort_fast`` take the kernel path for (n, dtype)?
    Any positive size qualifies (pad-to-pow2); only 4-byte words lower."""
    return n > 0 and jnp.dtype(dtype).itemsize == 4


def _default_pad(dtype):
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.float32):
        return jnp.float32(jnp.inf)
    return jnp.iinfo(dt).max


def local_sort_fast(keys: jax.Array, vals=None, *, interpret=None,
                    use_kernel: bool = True, pad_val=None):
    """Sort keys (u32/i32/f32) ascending, carrying an optional u32 payload.

    ``pad_val`` fills the pad-to-power-of-two tail (default: dtype max /
    +inf) — it must compare ≥ every real key; see the module docstring for
    the max-key payload caveat."""
    n = keys.shape[0]
    if not (use_kernel and supported(n, keys.dtype)):
        return bitonic_ref(keys, vals)
    m = max(LANES, _next_pow2(n))
    if m != n:
        if pad_val is None:
            pad_val = _default_pad(keys.dtype)
        keys = jnp.concatenate(
            [keys, jnp.full((m - n,), pad_val, keys.dtype)])
        if vals is not None:
            vals = jnp.concatenate(
                [vals, jnp.zeros((m - n,), vals.dtype)])
    out = _sort_pow2(keys, vals, interpret)
    if vals is None:
        return out[:n]
    return out[0][:n], out[1][:n]


def _sort_pow2(keys, vals, interpret):
    n = keys.shape[0]
    t = min(n, MAX_TILE)
    out = bitonic.sort_blocks(keys, vals, block=t, interpret=interpret)
    keys, vals = (out, None) if vals is None else out
    run = t
    while run < n:                  # merge ascending runs pairwise
        keys, vals = bitonic.merge_step(keys, vals, run, flip=True)
        dist = run // 2
        while dist >= t:
            keys, vals = bitonic.merge_step(keys, vals, dist)
            dist //= 2
        out = bitonic.clean_blocks(keys, vals, block=t, interpret=interpret)
        keys, vals = (out, None) if vals is None else out
        run *= 2
    return keys if vals is None else (keys, vals)


def bitonic_ref(keys, vals=None):
    from . import ref
    return ref.sort_tile_ref(keys, vals)
