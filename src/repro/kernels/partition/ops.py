"""Dispatcher for the fused partition-into-buckets primitive.

``partition_buckets`` is what the algorithms call (``rams._rams_level``,
``samplesort``'s destination map, ``rquick``'s split point).  It picks the
Pallas tile kernel (partition.py) or the jnp reference (ref.py) — bitwise
identical by tests/test_partition.py — and hides the tiling:

  * the shard is padded to whole tiles of ``_tile_rows(nb)`` rows (one
    tile of whole (8, 128) vregs when the shard is smaller);
  * a ``lax.scan`` over the tiles threads the running histogram through
    the launches, so ranks are global over the whole shard exactly like
    the reference's one argsort.

Kernel-vs-ref selection: an explicit ``use_kernel`` wins; ``None`` defers
to :func:`repro.core.types.local_kernels` (the ``REPRO_LOCAL_KERNELS``
policy — default on for TPU backends, off elsewhere).  The ref handles
every case; the kernel additionally requires two or three uint32 planes
— (key, tie) or (hi, lo, tie) — 2 ≤ nb ≤ ``MAX_BUCKETS`` and at least one
full lane row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .partition import LANES, key_planes, partition_tile
from .ref import partition_ref

# Tile sizes are those of the (R, 128, nb+1) one-hot formulation the kernel
# replaced (a ≈4 MiB budget for that one-hot).  The kernel now holds a few
# (R, 128) planes at any nb, so the rows need not shrink with nb; which
# size is fastest on the chip is not measured.
MAX_BUCKETS = 512
_VMEM_WORDS = 1 << 20


def _tile_rows(n_buckets: int) -> int:
    rows = _VMEM_WORDS // (LANES * (n_buckets + 1))
    return max(8, min(64, (rows // 8) * 8))


@jax.named_scope("partition_buckets")
def partition_buckets(keys, ties, s_keys, s_ties, *, n_buckets: int,
                      count=None, inclusive: bool = True,
                      want_pos: bool = True, interpret=None,
                      use_kernel=None):
    """Fused classify + rank + histogram over a locally-sorted shard.

    Same contract as :func:`repro.kernels.partition.ref.partition_ref`
    (see there for argument semantics: ``keys`` / ``s_keys`` are one u32
    plane or a tuple of planes); ``use_kernel`` selects the Pallas path
    (None → the ``local_kernels()`` policy)."""
    if use_kernel is None:
        from repro.core.types import local_kernels
        use_kernel = local_kernels().partition
    planes = key_planes(keys) + (ties,)
    C = planes[0].shape[0]
    eligible = (use_kernel and C >= LANES and 2 <= n_buckets <= MAX_BUCKETS
                and len(planes) <= 3
                and all(x.dtype == jnp.uint32 for x in planes)
                and all(x.dtype == jnp.uint32
                        for x in key_planes(s_keys) + (s_ties,)))
    if not eligible:
        return partition_ref(keys, ties, s_keys, s_ties, n_buckets=n_buckets,
                             count=count, inclusive=inclusive,
                             want_pos=want_pos)

    cnt = jnp.asarray(C if count is None else count, jnp.int32)
    rows = -(-C // LANES)
    R = _tile_rows(n_buckets)
    if rows <= R:               # one tile, rounded up to whole (8, 128) vregs
        R = -(-rows // 8) * 8
    n_tiles = -(-rows // R)
    tile = R * LANES
    pad = n_tiles * tile - C
    if pad:                     # pads classify as trash (flat ≥ nvalid)
        fill = jnp.full((pad,), 0xFFFFFFFF, jnp.uint32)
        planes = tuple(jnp.concatenate([x, fill]) for x in planes)

    def step(hist, xs):         # one launch per tile, histogram threaded
        k, t, off = xs
        nv = jnp.clip(cnt - off, 0, tile).reshape(1, 1)
        b, q, hist = partition_tile(k, t, s_keys, s_ties, hist, nv,
                                    n_buckets=n_buckets, inclusive=inclusive,
                                    interpret=interpret)
        return hist, (b, q)

    hist0 = jnp.zeros((1, n_buckets + 1), jnp.int32)
    tiles = tuple(x.reshape(n_tiles, R, LANES) for x in planes)
    hist, (bucket, pos) = jax.lax.scan(
        step, hist0,
        (tiles[:-1], tiles[-1], jnp.arange(n_tiles, dtype=jnp.int32) * tile))
    bucket = bucket.reshape(-1)[:C]
    pos = pos.reshape(-1)[:C] if want_pos else None
    return bucket, pos, hist[0, :n_buckets]
