"""Robust Quicksort on Hypercubes (paper §VI, Algorithm 2).

Per-iteration structure (dims d-1 .. 0):
  1. splitter = approximate median of the (j+1)-dim subcube, via the
     butterfly window reduction of §III-B (identical on all subcube PEs);
  2. local tie-break split:  a = a_ℓ · s^m · a_r  →  L = a_ℓ·s^x,
     R = s^(m-x)·a_r with x chosen so |L| is closest to |a|/2 — the paper's
     zero-communication duplicate-key defense;
  3. exchange along dim j (0-bit PE keeps the two L's, 1-bit the two R's);
  4. merge with the partner's sequence.

Robustness preconditions: an initial random redistribution (§III-A) turns
worst-case inputs into average-case ones (Lemma 1–3 ⇒ O(1) subcube
imbalance w.h.p.), which is what makes a *fixed* capacity factor sound in
the SPMD/static-shape setting.

``robust=False`` gives NTB-Quick (no shuffle, no tie-breaking) for the
Fig. 2a robustness comparison.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import comm
from .hypercube import (butterfly_sum, exchange_shard, hypercube_shuffle)
from .median import (butterfly_median_window, lift, splitter_from_window)
from .types import SortShard, compact, local_sort, merge_shards, resize
from repro.kernels.partition import partition_buckets


class RQuickResult(NamedTuple):
    shard: SortShard
    overflow: jax.Array          # elements dropped anywhere (must be 0)


def _split_point(shard: SortShard, splitter_lifted: jax.Array,
                 tie_break: bool) -> jax.Array:
    """Index splitting local sorted data into L=[0,idx) and R=[idx,C).

    With tie-breaking, x ∈ [0, m_eq] is chosen so |L| is closest to m/2.
    Without, all duplicates of the splitter go right (x = 0).
    """
    # fused-partition classify against the single lifted splitter, as
    # (hi, lo) u32 planes; bucket 0 of the inclusive pass holds the
    # elements < s, of the strict pass the elements ≤ s — the histogram
    # counts only valid elements, so no count-clamping is needed
    lifted = lift(shard.keys)
    e_hi = (lifted >> np.uint64(32)).astype(jnp.uint32)
    e_lo = lifted.astype(jnp.uint32)
    s_hi = jnp.reshape(splitter_lifted >> np.uint64(32), (1,)).astype(jnp.uint32)
    s_lo = jnp.reshape(splitter_lifted, (1,)).astype(jnp.uint32)

    def n_below(inclusive):
        _, _, h = partition_buckets(e_hi, e_lo, s_hi, s_lo, n_buckets=2,
                                    count=shard.count, inclusive=inclusive,
                                    want_pos=False)
        return h[0].astype(jnp.int32)

    n_less = n_below(True)             # bucket 0 ⇔ elem < s
    if not tie_break:
        return n_less
    n_leq = n_below(False)             # bucket 0 ⇔ elem ≤ s
    x = jnp.clip(shard.count // 2 - n_less, 0, n_leq - n_less)
    return n_less + x


def rquick(shard: SortShard, axis_name: str, p: int, *,
           seed: int = 0x5EED, window_k: int = 16,
           robust: bool = True, shuffle: Optional[bool] = None,
           tie_break: Optional[bool] = None,
           capacity: Optional[int] = None,
           dims: Optional[Sequence[int]] = None) -> RQuickResult:
    """Sort over the (sub)cube spanned by ``dims`` (default: the whole axis).

    Must be called inside shard_map.  Output: ascending over PE order,
    each shard locally sorted; elements never cross the subcube boundary.
    The input need not be sorted.

    The working capacity (default) is twice the input's when there is a
    dimension to exchange along, for the imbalance the splits leave; with
    empty ``dims`` (p = 1, or a subcube of one PE) nothing is exchanged,
    so the shard keeps its capacity and is sorted once at that size.
    """
    d_all = p.bit_length() - 1
    dims = list(dims) if dims is not None else list(range(d_all))
    shuffle = robust if shuffle is None else shuffle
    tie_break = robust if tie_break is None else tie_break
    cap = capacity or (2 * shard.capacity if dims else shard.capacity)
    overflow = jnp.int32(0)

    shard, _ = resize(shard, cap)
    if shuffle:
        shard, ovf = hypercube_shuffle(shard, axis_name, p, seed, dims=dims)
        overflow = overflow + ovf
    shard = local_sort(shard)

    me = comm.axis_index(axis_name)
    for it, j in enumerate(sorted(dims, reverse=True)):
        sub_dims = [t for t in dims if t <= j]
        # --- splitter selection in parallel (§III-B) --------------------
        w = butterfly_median_window(shard, axis_name, p, sub_dims, window_k,
                                    seed=seed * 1000003 + it)
        s, w_empty = splitter_from_window(w, seed=seed * 1000003 + it)
        sub_count = butterfly_sum(shard.count, axis_name, p, sub_dims)
        is_empty = (sub_count == 0) | w_empty

        # --- local tie-break split --------------------------------------
        idx = _split_point(shard, s, tie_break)
        pos = jnp.arange(cap, dtype=jnp.int32)
        i_am_upper = ((me >> j) & 1) == 1
        # lower PE sends R (suffix), upper PE sends L (prefix)
        send_mask = jnp.where(i_am_upper, pos < idx, pos >= idx)
        send_mask = jnp.where(is_empty, jnp.zeros_like(send_mask), send_mask)
        sent = compact(shard, send_mask)
        kept = compact(shard, ~send_mask)
        recv = exchange_shard(sent, axis_name, p, j)
        shard, ovf = merge_shards(kept, recv, capacity=cap)
        overflow = overflow + ovf
    return RQuickResult(shard, overflow)
