"""Pallas TPU kernels: bitonic sort network + bitonic half-cleaner merge.

Local sorting/merging is the compute hot spot of every algorithm in the
paper (the O((n/p)·log n) term of Table I).  A block of B = R·128 words is
held in VMEM as (R, 128) — flat element index f = r·128 + l — and the
Batcher network runs on it in vector ops only.  The compare-exchange
partner f ^ 2^j is found with two rotations (``pltpu.roll``) and a select
on bit j of f:

  * distance 2^j < 128: rotate along lanes by 2^j (partner below) and by
    128 - 2^j (partner above);
  * distance 2^j ≥ 128: the same along sublanes, by 2^j / 128 rows.

No gathers, no reversals, no scalar loops: the network is unrolled at trace
time (log²(B)/2 steps).  Ties are broken by flat index, so both partners of
a pair make the same swap decision and (key, payload) pairs stay together.

Two kernels, each with a grid over independent blocks (no ``program_id``
in the body, so a ``vmap`` that prepends a batch axis to the grid keeps
them correct):

  * :func:`sort_blocks` sorts every block ascending;
  * :func:`clean_blocks` runs the half-cleaner chain (distances B/2 … 1) on
    every block, which sorts any bitonic block ascending.

Merging two ascending runs pairs ``a[i]`` with ``b[n-1-i]`` (the "flip"
step); that step, and every step at a distance of a block or more, is a
plain XLA elementwise pass outside the kernels (:func:`merge_step`).
:func:`merge_tiles` composes one flip step with :func:`clean_blocks`.

Keys are 4-byte words (u32/i32/f32); an optional 4-byte payload travels
along.  Sorting is a pure VPU workload; the kernels keep a block in VMEM
across its O(log² B) passes instead of a round trip to HBM per pass.  The
kernels compile for TPU through Mosaic; off-TPU they run in the Pallas
interpreter (:func:`repro.kernels.interpret_mode`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

LANES = 128


def _flat_bit(shape, j: int) -> jax.Array:
    """(f >> j) & 1 for the (R, 128) layout, as a bool plane."""
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    l = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (((r * LANES + l) >> j) & 1) == 1


def _partner(x: jax.Array, j: int, upper: jax.Array) -> jax.Array:
    """x[f ^ 2^j] for every f; ``upper`` is bit j of f."""
    if (1 << j) >= LANES:                       # partner in another row
        m, axis, size = (1 << j) // LANES, 0, x.shape[0]
    else:                                       # partner in another lane
        m, axis, size = 1 << j, 1, LANES
    # roll(x, s)[i] = x[i - s]: the upper element reads 2^j below, the
    # lower one 2^j above
    # (int32 shifts: under jax_enable_x64 a Python int would lower as i64)
    return jnp.where(upper, pltpu.roll(x, np.int32(m), axis),
                     pltpu.roll(x, np.int32(size - m), axis))


def _compare_exchange(keys, vals, j: int, want_min):
    """One network step at distance 2^j. ``want_min``: bool plane."""
    upper = _flat_bit(keys.shape, j)            # my bit j set ⇒ I am f|2^j
    pk = _partner(keys, j, upper)
    # strict order with index tie-break: am I the smaller of the pair?
    am_lower = (keys < pk) | ((keys == pk) & ~upper)
    take_self = am_lower == want_min
    out_k = jnp.where(take_self, keys, pk)
    out_v = None
    if vals is not None:
        out_v = jnp.where(take_self, vals, _partner(vals, j, upper))
    return out_k, out_v


def _sort_network(keys, vals):
    d = int(math.log2(keys.size))
    for k in range(d):                          # stage: bitonic blocks 2^(k+1)
        for j in range(k, -1, -1):
            up = ~_flat_bit(keys.shape, k + 1)  # block direction
            want_min = ~_flat_bit(keys.shape, j) == up
            keys, vals = _compare_exchange(keys, vals, j, want_min)
    return keys, vals


def _clean_network(keys, vals):
    """Half-cleaner chain: sorts a bitonic block ascending."""
    d = int(math.log2(keys.size))
    for j in range(d - 1, -1, -1):
        keys, vals = _compare_exchange(keys, vals, j,
                                       ~_flat_bit(keys.shape, j))
    return keys, vals


def _kernel(network, keys_ref, vals_ref, out_k_ref, out_v_ref):
    k, v = network(keys_ref[...],
                   vals_ref[...] if vals_ref is not None else None)
    out_k_ref[...] = k
    if out_v_ref is not None:
        out_v_ref[...] = v


def _run_blocks(network, keys, vals, block: int, interpret):
    """Apply ``network`` to every ``block``-word block of ``keys`` (and
    ``vals``) in one ``pallas_call`` with a grid over the blocks."""
    n = keys.shape[0]
    assert block % LANES == 0 and block & (block - 1) == 0, block
    assert n % block == 0, "blocks must tile the input"
    rows = block // LANES
    # int32 block index (a Python 0 would lower as i64 under x64)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, np.int32(0)))
    planes = (keys,) if vals is None else (keys, vals)
    if vals is None:
        def kern(kr, ok):
            _kernel(network, kr, None, ok, None)
    else:
        kern = functools.partial(_kernel, network)
    out = pl.pallas_call(
        kern,
        out_shape=tuple(jax.ShapeDtypeStruct((n // LANES, LANES), x.dtype)
                        for x in planes),
        in_specs=[spec] * len(planes), out_specs=(spec,) * len(planes),
        grid=(n // block,),
        interpret=interpret_mode(interpret),
    )(*(x.reshape(n // LANES, LANES) for x in planes))
    out = tuple(o.reshape(n) for o in out)
    return out[0] if vals is None else out


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sort_blocks(keys, vals=None, *, block: int, interpret=None):
    """Sort every ``block``-word block of ``keys`` ascending (with an
    optional payload plane)."""
    return _run_blocks(_sort_network, keys, vals, block, interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def clean_blocks(keys, vals=None, *, block: int, interpret=None):
    """Sort every bitonic ``block``-word block of ``keys`` ascending."""
    return _run_blocks(_clean_network, keys, vals, block, interpret)


def merge_step(keys, vals, dist: int, flip: bool = False):
    """One compare-exchange step over the whole array, min to the lower
    index, between element i of every 2·dist-word group and element
    i + dist — or, with ``flip``, element 2·dist-1-i, which pairs an
    ascending run with the reverse of the next one.  After a flip step both
    runs of a pair are bitonic and every element of the first is ≤ every
    element of the second.  An XLA pass, for distances of a kernel block or
    more."""
    n = keys.shape[0]

    def halves(x):
        x = x.reshape(n // (2 * dist), 2, dist)
        return x[:, 0], (x[:, 1, ::-1] if flip else x[:, 1])

    def join(a, b):
        return jnp.stack([a, b], axis=1).reshape(n)

    lo, hi = halves(keys)
    keep = lo <= hi
    keys = join(jnp.where(keep, lo, hi), jnp.where(keep, hi, lo))
    if vals is None:
        return keys, None
    lo, hi = halves(vals)
    return keys, join(jnp.where(keep, lo, hi), jnp.where(keep, hi, lo))


@functools.partial(jax.jit, static_argnames=("interpret",))
def sort_tile(keys: jax.Array, vals=None, *, interpret=None):
    """Sort a (R·128,)-element tile (R a power of two) in one VMEM block."""
    return sort_blocks(keys, vals, block=keys.shape[0], interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_tiles(a: jax.Array, b: jax.Array, av=None, bv=None, *,
                interpret=None):
    """Merge two ascending tiles of equal power-of-two size (≥128 each)."""
    n = a.shape[0]
    assert a.shape == b.shape and n % LANES == 0
    keys = jnp.concatenate([a, b])
    vals = None if av is None else jnp.concatenate([av, bv])
    keys, vals = merge_step(keys, vals, n, flip=True)
    # one block over both runs: its first step (distance n) finds every
    # pair already ordered, and the block spans the whole array at any n
    return clean_blocks(keys, vals, block=2 * n, interpret=interpret)
