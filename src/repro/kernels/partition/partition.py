"""Pallas TPU kernel: fused splitter classify + histogram + in-bucket rank.

One VMEM-resident pass over an (R, 128) tile does everything the all_to_all
routing needs (the IPS⁴o block-partition shape, arXiv 2009.13569, mapped
onto the VPU):

  * classify: branchless SSSS ``#splitters ≤ elem`` as a lexicographic
    compare over u32 planes — (key, tie) for 32-bit keys, (hi, lo, tie)
    for 64-bit ones — of the tile against each of the S = nb-1 splitters,
    read as scalars from SMEM, one SMEM row per plane: no wide composite
    materializes, the planes compare directly;
  * histogram + stable rank: for each bucket b, the 0/1 plane ``bucket ==
    b`` gets an inclusive prefix sum along lanes and an exclusive one over
    the row totals along sublanes, both as log-step roll-and-add scans.
    Their sum is each element's stable in-bucket rank in flat (row-major)
    order, and the last row's total is the tile's count of b.  Elements at
    flat index ≥ ``nvalid`` (shard padding) land in the **trash bucket**
    nb.

Work is O(R·128·nb) per tile, like the one-hot formulation it replaces,
but nothing wider than the tile is ever live.

The kernel is deliberately ``grid=(1,)`` whole-tile — kernels/bitonic's
grid steps are likewise independent, unlike kernels/kway's
``program_id``-based histogram accumulation — so it stays correct
under vmap batching (the sim backend wraps every PE body in one vmap; jax
prepends batch dims to the pallas grid, which breaks program_id-relative
offsets but leaves whole-tile launches untouched).  ops.py chains tiles by
threading the running histogram through a ``lax.scan`` of launches;
``prev_hist[bucket] + rank_in_tile`` is then the global stable send
position.

The ``pallas_call`` is named :data:`KERNEL_NAME`, which is what a chip
trace calls its op.  On a TPU the kernel compiles through Mosaic; elsewhere
it runs in the Pallas interpreter (:func:`repro.kernels.interpret_mode`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

LANES = 128
KERNEL_NAME = "partition_planes"   # the op's name in a chip trace
_ZERO = np.int32(0)          # int32 block index: a Python 0 is i64 under x64


def _scan(x, axis: int, size: int):
    """Inclusive prefix sum of ``x`` along ``axis`` (log-step roll-and-add)."""
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    s = 1
    while s < size:
        x = x + jnp.where(idx >= s, pltpu.roll(x, np.int32(s), axis), _ZERO)
        s *= 2
    return x


def loop(n: int, body, init):
    """``fori_loop(0, n, body, init)`` with an int32 counter: under
    jax_enable_x64 fori_loop's own counter is i64, which Mosaic rejects."""
    def step(carry, _):
        i, x = carry
        return (i + np.int32(1), body(i, x)), None
    return jax.lax.scan(step, (np.int32(0), init), None, length=n)[0][1]


def classify(planes, s_refs, n_split: int, inclusive: bool = True):
    """#splitters ≤ e (``inclusive``) or < e, lexicographically over the u32
    ``planes`` of e (most significant first, the tie plane last), for a
    tile of planes against ``n_split`` splitters held as (1, S) SMEM rows,
    one row per plane."""
    def step(s, bucket):
        sv = [r[0, s] for r in s_refs]
        le = (sv[-1] <= planes[-1]) if inclusive else (sv[-1] < planes[-1])
        for sp, ep in zip(sv[-2::-1], planes[-2::-1]):
            le = (sp < ep) | ((sp == ep) & le)
        return bucket + le.astype(jnp.int32)
    return loop(n_split, step, jnp.zeros(planes[0].shape, jnp.int32))


def _partition_kernel(*refs, n_planes: int, n_buckets: int, inclusive: bool):
    planes, s_refs = refs[:n_planes], refs[n_planes:2 * n_planes]
    ph_ref, nv_ref, bucket_ref, pos_ref, hist_ref = refs[2 * n_planes:]
    R = planes[0].shape[0]
    shape = (R, LANES)
    bucket = classify([r[...] for r in planes], s_refs, n_buckets - 1,
                      inclusive)
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    l = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    bucket = jnp.where(r * LANES + l < nv_ref[0, 0], bucket,
                       jnp.int32(n_buckets))
    bucket_ref[...] = bucket

    prev = ph_ref[...]                               # (1, H) running hist
    hlane = jax.lax.broadcasted_iota(jnp.int32, prev.shape, 1)

    def rank(b, carry):
        pos, hist = carry
        mine = bucket == b
        in_row = _scan(mine.astype(jnp.int32), 1, LANES)    # inclusive
        row_tot = jnp.broadcast_to(in_row[:, LANES - 1:], shape)
        upto_row = _scan(row_tot, 0, R)                      # inclusive
        base = jnp.sum(jnp.where(hlane == b, prev, _ZERO), axis=1,
                       keepdims=True, dtype=jnp.int32)       # (1, 1)
        # rank = earlier tiles + earlier rows + earlier-in-row
        rank_b = base + (upto_row - row_tot) + in_row - np.int32(1)
        pos = jnp.where(mine, rank_b, pos)
        hist = hist + jnp.where(hlane == b, upto_row[R - 1:, :1], _ZERO)
        return pos, hist

    pos, hist = loop(n_buckets + 1, rank,
                     (jnp.zeros(shape, jnp.int32), prev))
    pos_ref[...] = pos
    hist_ref[...] = hist


def key_planes(keys) -> tuple:
    """The key planes of an argument that is one u32 plane or a tuple."""
    return tuple(keys) if isinstance(keys, (tuple, list)) else (keys,)


@functools.partial(jax.jit,
                   static_argnames=("n_buckets", "inclusive", "interpret"))
def partition_tile(keys2, ties2, s_keys, s_ties, prev_hist, nvalid, *,
                   n_buckets: int, inclusive: bool = True, interpret=None):
    """Partition one (R, 128) tile.  ``keys2`` is one (R, 128) u32 key
    plane or a tuple of them (most significant first), ``s_keys`` the
    splitters' matching (S,) planes; elements compare lexicographically on
    (key planes…, tie).  ``prev_hist`` is the (1, nb+1) running histogram
    of earlier tiles (trash bucket included); ``nvalid`` is a (1, 1) int32
    count of valid elements in this tile (flat order).
    Returns (bucket (R,128), pos (R,128), new_hist (1, nb+1))."""
    planes = key_planes(keys2) + (ties2,)
    s_planes = key_planes(s_keys) + (s_ties,)
    R = planes[0].shape[0]
    nbt = n_buckets + 1
    width = -(-nbt // LANES) * LANES                 # lane-aligned histogram
    hist = jnp.pad(prev_hist, ((0, 0), (0, width - nbt)))
    blk = pl.BlockSpec((R, LANES), lambda i: (i, _ZERO))
    whole = lambda i: (_ZERO, _ZERO)                 # noqa: E731
    hblk = pl.BlockSpec((1, width), whole)
    sblk = pl.BlockSpec((1, n_buckets - 1), whole, memory_space=pltpu.SMEM)
    one = pl.BlockSpec((1, 1), whole, memory_space=pltpu.SMEM)
    kern = functools.partial(_partition_kernel, n_planes=len(planes),
                             n_buckets=n_buckets, inclusive=inclusive)
    bucket, pos, hist = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((R, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((R, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((1, width), jnp.int32)),
        in_specs=[blk] * len(planes) + [sblk] * len(planes) + [hblk, one],
        out_specs=(blk, blk, hblk),
        grid=(1,), interpret=interpret_mode(interpret),
        name=KERNEL_NAME,
    )(*planes, *(s.reshape(1, -1) for s in s_planes), hist, nvalid)
    return bucket, pos, hist[:, :nbt]
