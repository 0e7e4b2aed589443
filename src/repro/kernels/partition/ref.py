"""jnp reference for the fused partition-into-buckets primitive.

This is the semantics contract the Pallas kernel (partition.py) is diffed
against, and the implementation the sim backend / CPU CI actually run.  It
replaces the O(n·nb) one-hot/broadcast formulation that used to live in
``rams._rams_level`` (bucket via ``jnp.sum(splitters[None,:] <= elem[:,None])``,
rank via an nb-wide one-hot ``cumsum``) with O(n·log) primitives:

  * classify: binary-search the nb-1 sorted splitters (SSSS ``#splitters ≤
    elem``, expressed as ``searchsorted(..., side="right")`` — identical
    because the splitter sequence is nondecreasing);
  * rank + histogram: one stable argsort of the bucket ids, then
    first-occurrence subtraction (the ``_alltoall_route`` ranking idiom).

Keys and tie-break tags arrive as separate uint32 planes — the same layout
the Pallas kernel consumes — and compare lexicographically: (key, tag) for
32-bit keys, which for (key << 32 | tag) composites equals the u64 compare,
and (hi, lo, tag) for 64-bit keys.  There the 96-bit compare is two u64
searches: the key's first splitter run (``searchsorted`` on the (hi, lo)
words), then, inside a run of equal splitter keys, the tags (a search on
(run start << 32 | tag)).

Invalid elements (flat index ≥ ``count``) go to the **trash bucket**
``n_buckets``; they get real ranks there (stable, in flat order) so the
reference and the kernel agree everywhere, but the returned histogram covers
the real buckets only: ``sum(hist) == count``.
"""
from __future__ import annotations

import jax.numpy as jnp


def partition_ref(keys, ties, s_keys, s_ties, *, n_buckets: int,
                  count=None, inclusive: bool = True, want_pos: bool = True):
    """Classify + rank + histogram in one pass (pure jnp).

    Args:
      keys, ties: (C,) uint32 planes of the element composites
        (``key << 32 | tie``); ties may be all-zero when tie-breaking is off.
        ``keys`` may be a tuple of two planes (hi, lo) of a 64-bit key.
      s_keys, s_ties: (S,) uint32 planes of the S = n_buckets-1 splitter
        composites, nondecreasing under the (key, tie) lex order; a tuple
        (hi, lo) where ``keys`` is one.
      n_buckets: number of real buckets; invalid elements land in bucket
        ``n_buckets``.
      count: number of valid elements (prefix of the array), or None for all.
      inclusive: True → bucket = #{s : s ≤ e} (SSSS); False → #{s : s < e}.
      want_pos: skip the rank computation (callers that only need
        bucket/hist, e.g. samplesort's destination map).

    Returns:
      (bucket, pos, hist): bucket (C,) int32 in [0, n_buckets]; pos (C,)
      int32 stable rank within the element's bucket (None when
      ``want_pos=False``); hist (n_buckets,) int32 with
      ``sum(hist) == count``.
    """
    side = "right" if inclusive else "left"
    if isinstance(keys, (tuple, list)) and len(keys) == 2:
        bucket = _classify_wide(keys, ties, s_keys, s_ties, side)
    else:
        if isinstance(keys, (tuple, list)):
            (keys,), (s_keys,) = keys, s_keys
        elem = (keys.astype(jnp.uint64) << 32) | ties.astype(jnp.uint64)
        spl = (s_keys.astype(jnp.uint64) << 32) | s_ties.astype(jnp.uint64)
        bucket = jnp.searchsorted(spl, elem, side=side).astype(jnp.int32)
    C = bucket.shape[0]
    if count is not None:
        valid = jnp.arange(C, dtype=jnp.int32) < count
        bucket = jnp.where(valid, bucket, jnp.int32(n_buckets))
    # one stable argsort gives both the histogram (run bounds) and the
    # in-bucket rank (distance to the run start) without any (C, nb) blowup
    order = jnp.argsort(bucket, stable=True)
    sb = bucket[order]
    bounds = jnp.searchsorted(sb, jnp.arange(n_buckets + 1, dtype=jnp.int32),
                              side="left")
    hist = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
    if not want_pos:
        return bucket, None, hist
    first = jnp.searchsorted(sb, sb, side="left")
    rank = jnp.arange(C, dtype=jnp.int32) - first.astype(jnp.int32)
    pos = jnp.zeros((C,), jnp.int32).at[order].set(rank)
    return bucket, pos, hist


def _classify_wide(keys, ties, s_keys, s_ties, side: str):
    """SSSS bucket of (hi, lo, tie) elements against (hi, lo, tie)
    splitters.  An element whose 64-bit key equals no splitter's lands
    after the ``first`` splitters with a smaller key; one that equals the
    run of splitters starting at ``first`` is placed among them by its tie,
    comparing (run start, tie) words, which every splitter outside the run
    orders correctly too."""
    def wide(hi, lo):
        return (hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)
    key, s_key = wide(*keys), wide(*s_keys)
    run = jnp.searchsorted(s_key, s_key, side="left")
    first = jnp.searchsorted(s_key, key, side="left")
    hit = s_key[jnp.minimum(first, s_key.shape[0] - 1)] == key
    in_run = jnp.searchsorted(wide(run, s_ties), wide(first, ties), side=side)
    return jnp.where(hit & (first < s_key.shape[0]), in_run,
                     first).astype(jnp.int32)
