"""RAMS — Robust Multi-level (AMS) Sample Sort (paper §V / App. G).

Per level, within the current subcube of size p_sub (split into k = 2^b
groups):
  1. sample locally *with tie-breakers*: a sample is its key and a 32-bit
     tag mixed from (pe, pos) — one u64 composite (key << 32 | tag) for
     u32 keys, three u32 planes (hi, lo, tag) for u64 keys.  Tie-break
     info is attached to the O(k log k) samples only, never to the data
     elements (the paper's low-overhead scheme);
  2. all-gather the samples inside the subcube (grouped collective — the
     TPU analogue of ranking samples with FIS: one fused all-gather beats
     emulating the 2-D grid for tiny arrays, cf. DESIGN.md §2);
  3. select n_b = b·k splitters, classify local elements into n_b buckets
     (Super Scalar Sample Sort classifier with implicit tie-breaking:
     an element's tag is formed *locally* from (own_pe, own_pos), and its
     u32 planes compare lexicographically against the splitters');
  4. psum the bucket histogram, greedily assign contiguous bucket ranges to
     the k groups (ε-balance: imbalance ≤ max bucket ≈ total/(b·k));
  5. compute each element's target PE inside its group from its *global*
     position (hypercube prefix-scan of histograms) — perfect balance within
     target groups, the property that distinguishes AMS from HykSort;
  6. exchange via one fused all-to-all with Chernoff-provisioned slots.

Static-shape adaptation (DESIGN.md §2): deterministic message assignment
and NBX are replaced by the static SPMD schedule (all-to-all *is* a
deterministic assignment with Θ(k) partners); a one-time random
redistribution at the first level makes the fixed slot capacities sound on
adversarial inputs (same Lemma-1 argument as RQuick — each PE then holds a
random sample of its subcube's data at every level).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import comm
from .hypercube import (_alltoall_route, alltoall_shuffle, subcube_groups,
                        subcube_prefix_sum)
from .types import SortShard, local_sort, resize
from repro.kernels.partition import partition_buckets

_PE_BITS = 12
_POS_BITS = 20
_HI64 = np.uint64(0xFFFFFFFFFFFFFFFF)
_ONES32 = np.uint32(0xFFFFFFFF)


class RAMSResult(NamedTuple):
    shard: SortShard
    overflow: jax.Array


def default_levels(p: int, levels: Optional[int] = None) -> Sequence[int]:
    """Split log2(p) into `levels` groups of bits, high bits first."""
    d = p.bit_length() - 1
    if levels is None:
        levels = 1 if d <= 4 else (2 if d <= 10 else 3)
    levels = max(1, min(levels, d)) if d else 1
    base, rem = divmod(d, levels)
    return [base + (1 if i < rem else 0) for i in range(levels)]


def nested_level_bits(p_outer: int, p_inner: int,
                      levels: Optional[int] = None) -> Sequence[int]:
    """Level schedule aligned to a nested (outer × inner) axis pair.

    The multi-level mapping of arXiv 1410.6754 §4: the **first** level
    splits the data across the 2^b0 = ``p_outer`` slow-axis slices (its
    all_to_all is the only exchange that crosses the outer axis); every
    subsequent level recurses inside one inner-axis subcube, so its
    collectives retarget onto the fast intra axis (see
    ``repro.core.comm.NestedCollectives``).  With ``levels=1`` the single
    level spans both axes (one all-to-all over the whole mesh — the
    samplesort structure).

    >>> nested_level_bits(16, 64)
    [4, 3, 3]
    >>> nested_level_bits(16, 64, levels=2)
    [4, 6]
    >>> nested_level_bits(4, 16, levels=1)
    [6]
    """
    d_o = p_outer.bit_length() - 1
    d_i = p_inner.bit_length() - 1
    assert p_outer.bit_count() == 1 and p_inner.bit_count() == 1
    if d_o == 0:
        return list(default_levels(p_inner, levels))
    if d_i == 0:
        return [d_o]
    if levels == 1:
        return [d_o + d_i]
    inner_levels = None if levels is None else max(1, levels - 1)
    return [d_o] + list(default_levels(p_inner, inner_levels))


def _mix32(x):
    """Bijective 32-bit mix (murmur3 finalizer).

    The tie-break tag only needs to induce *some* total order on duplicates
    (App. G) — but the raw (pe, pos) word orders one PE's duplicates as a
    contiguous run, so on duplicate-heavy inputs an entire source shard
    routes to one destination and overflows its a2a slot (observed at
    p = 64 on Zero).  Mixing keeps the tag injective while decorrelating
    the order from (pe, pos), so duplicates scatter uniformly over buckets
    and the Chernoff slot provisioning applies again.
    """
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _tag(pe, pos):
    """The 32-bit tie-break tag of the element at ``pos`` on PE ``pe``."""
    return _mix32((pe.astype(jnp.uint32) << np.uint32(_POS_BITS))
                  | pos.astype(jnp.uint32))


def _composite(keys_u32, pe, pos, valid):
    tag = _tag(pe, pos)
    c = (keys_u32.astype(jnp.uint64) << np.uint64(_PE_BITS + _POS_BITS)) \
        | tag.astype(jnp.uint64)
    return jnp.where(valid, c, _HI64)


def _split64(keys_u64):
    """(hi, lo) u32 planes of u64 keys."""
    return ((keys_u64 >> np.uint64(32)).astype(jnp.uint32),
            keys_u64.astype(jnp.uint32))


def _sample_planes(keys_u64, pe, pos, valid, tie_break: bool):
    """(s, 3) u32 samples (hi, lo, tag) of u64 keys; all-ones when
    invalid, a zero tag without tie-breaking."""
    tag = _tag(pe, pos) if tie_break else jnp.zeros(pos.shape, jnp.uint32)
    planes = jnp.stack(_split64(keys_u64) + (tag,), axis=1)
    return jnp.where(valid[:, None], planes, _ONES32)


def quantile_splitters(sorted_samples, nb: int, invalid=_HI64):
    """``nb - 1`` evenly spaced order statistics of the valid prefix.

    The shared splitter pick of RAMS, samplesort, and the external lane:
    ``sorted_samples`` is an ascending u64 composite array whose invalid
    entries equal ``invalid`` (and therefore sort to the tail), or a tuple
    of u32 planes in lexicographic order (RAMS on 64-bit keys) whose
    invalid entries are all-ones in every plane; the i-th splitter is the
    entry at rank ``i * n_valid // nb``, as a tuple of planes for a tuple.
    Extracted so the callers stay bitwise-identical.
    """
    def ranks(n_valid, size):
        q = (jnp.arange(1, nb, dtype=jnp.int64) * n_valid) // nb
        return jnp.clip(q, 0, size - 1)

    if isinstance(sorted_samples, tuple):
        valid = functools.reduce(jnp.logical_or,
                                 [s != _ONES32 for s in sorted_samples])
        at = ranks(jnp.sum(valid), sorted_samples[0].shape[0])
        return tuple(s[at] for s in sorted_samples)
    at = ranks(jnp.sum(sorted_samples != invalid), sorted_samples.shape[0])
    return sorted_samples[at]


def rams(shard: SortShard, axis_name: str, p: int, *,
         seed: int = 0xA35, levels: Optional[int] = None,
         level_bits: Optional[Sequence[int]] = None,
         oversample: int = 4, tie_break: bool = True,
         shuffle: bool = True, slot_factor: float = 2.0,
         overlap: bool = False) -> RAMSResult:
    """Sort over the whole axis: uint32 keys, or uint64 keys (psort's
    transform of f64 / i64 / u64), which classify as (hi, lo, tie) u32
    planes.

    ``level_bits`` overrides the level schedule with an explicit per-level
    bit split (summing to log2 p, high bits first) — on a hierarchical
    mesh the caller aligns the first level to the outer-axis size with
    :func:`nested_level_bits`, which is what confines every later level's
    collectives to the fast intra axis.  The schedule, not the mesh, is
    what the sort depends on: a flat run with the same ``level_bits`` is
    bitwise-identical to the nested run.

    Each phase is traced under a :func:`repro.core.comm.tagged` scope
    (``shuffle``, ``level0``, ``level1``, …), so a counting backend
    attributes per-level launches and bytes.

    ``overlap=True`` streams every slotted exchange (shuffle and levels)
    through :func:`repro.core.comm.alltoall_stream`, folding arriving PE
    blocks into a running merge instead of gathering-then-sorting —
    bitwise-identical output, see ``hypercube._stream_route_merge``.
    """
    d = p.bit_length() - 1
    assert p.bit_count() == 1 and shard.capacity < (1 << _POS_BITS)
    if level_bits is not None:
        bits = [int(b) for b in level_bits]
        if sum(bits) != d or any(b < 1 for b in bits):
            raise ValueError(f"level_bits {bits} must be >=1 each and sum "
                             f"to log2(p)={d}")
    else:
        bits = default_levels(p, levels)
    cap = shard.capacity
    overflow = jnp.int32(0)
    me = comm.axis_index(axis_name)

    if shuffle:
        with comm.tagged("shuffle"):
            shard, ovf = alltoall_shuffle(
                shard, axis_name, p, seed,
                slot_cap=_slot_cap(cap, p, slot_factor), stream=overlap)
        overflow = overflow + ovf
        if not overlap:                     # streamed arrives sorted
            shard = local_sort(shard)
    else:
        shard = local_sort(shard)
    # drop the shuffle's p·slot_cap slot buffer down to 2× the working
    # capacity — at p = 1024 the inflated buffer (≈112·cap) would otherwise
    # flow through every level's classifier and exchange.  The 2× keeps the
    # provisioning slack the levels' slot caps are scaled from (shrinking
    # all the way to cap tightens _slot_cap enough to overflow on dense
    # uniform inputs).
    shard, ovf = resize(shard, min(shard.capacity, 2 * cap))
    overflow = overflow + ovf

    h = d                                   # dims of the current subcube
    for lvl, b in enumerate(bits):
        with comm.tagged(f"level{lvl}"):
            shard, ovf = _rams_level(shard, axis_name, p, h, b,
                                     seed=seed + 7919 * (lvl + 1),
                                     oversample=oversample,
                                     tie_break=tie_break,
                                     slot_factor=slot_factor,
                                     overlap=overlap)
        overflow = overflow + ovf
        h -= b
    return RAMSResult(shard, overflow)


def _slot_cap(cap: int, p_sub: int, slot_factor: float) -> int:
    mean = max(1.0, cap / p_sub)
    return int(math.ceil(slot_factor * mean + 6 * math.sqrt(mean) + 6))


def _rams_level(shard: SortShard, axis_name: str, p: int, h: int, b: int,
                *, seed, oversample, tie_break, slot_factor,
                overlap: bool = False):
    """One k-way splitting level within the 2^h-subcubes."""
    k = 1 << b
    p_sub = 1 << h
    p_g = p_sub >> b                       # PEs per target group
    # b·k buckets (paper §V): per-level group imbalance is bounded by one
    # bucket ≈ (1 + 1/b)× — with L levels the bounds *compound* to
    # (1 + 1/b)^L, so b = 2 (1.5²≈2.25×) breaks the 2× capacity provision
    # at two levels; b = 4 keeps the product at 1.25²≈1.56×.
    nb = max(k, oversample * k)
    cap = shard.capacity
    me = comm.axis_index(axis_name)
    sub_rel = me & (p_sub - 1)             # my index within the subcube
    groups = subcube_groups(p, h)
    sub_dims = list(range(h))

    # --- 1. local samples with tie-break tags -----------------------------
    # sample count scales with the *bucket* count nb (not just k): splitter
    # quantiles must resolve bucket-width mass, else the last level
    # (p_g = 1, where group total == PE load) inherits the full sampling
    # error and breaks the 2× capacity bound (observed at p = 64).
    # A u32 key and its tag pack into one u64 composite; a u64 key and its
    # tag are three u32 planes (hi, lo, tag), one (s_per, 3) row each.
    wide = shard.keys.dtype == jnp.uint64
    s_per = max(1, -(-(2 * nb * max(2, int(math.log2(p_sub + 1)))) // p_sub))
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), me), 1)
    pos = jax.random.randint(key, (s_per,), 0, jnp.maximum(shard.count, 1))
    sample_keys = shard.keys[pos]
    valid = (shard.count > 0)
    sample_pe = jnp.broadcast_to(sub_rel, (s_per,))
    if wide:
        samp = _sample_planes(sample_keys, sample_pe, pos,
                              valid & (pos < shard.count), tie_break)
    else:
        samp = _composite(sample_keys, sample_pe, pos,
                          valid & (pos < shard.count))
        if not tie_break:
            samp = jnp.where(
                samp == _HI64, samp,
                samp & ~np.uint64((1 << (_PE_BITS + _POS_BITS)) - 1))

    # --- 2. gather + sort samples within subcube ---------------------------
    all_samp = comm.all_gather(samp, axis_name, axis_index_groups=groups,
                               tiled=True)
    with jax.named_scope("splitters"):
        if wide:
            all_samp = tuple(jax.lax.sort(tuple(all_samp.T), num_keys=3))
        else:
            all_samp = jnp.sort(all_samp)

        # --- 3. select splitters, classify ---------------------------------
        splitters = quantile_splitters(all_samp, nb)              # (nb-1,)
    # fused SSSS classify + histogram + stable in-bucket rank.  Element
    # composites never materialize: the (key, tag) or (hi, lo, tag) planes
    # compare lexicographically, which equals the composite compare since
    # the tag is exactly 32 bits.  Invalid entries (flat index ≥ count —
    # pads sit at the tail of a locally-sorted shard) go to the trash
    # bucket nb.
    elem_pos = jnp.arange(cap, dtype=jnp.int32)
    if tie_break:
        e_ties = _tag(jnp.broadcast_to(sub_rel, (cap,)), elem_pos)
    else:
        e_ties = jnp.zeros((cap,), jnp.uint32)
    if wide:
        e_keys, s_keys, s_ties = _split64(shard.keys), splitters[:2], \
            splitters[2]
    else:
        e_keys = shard.keys
        s_keys = (splitters >> np.uint64(_PE_BITS + _POS_BITS)
                  ).astype(jnp.uint32)
        s_ties = splitters.astype(jnp.uint32)        # low 32 bits
    bucket, q_in_bucket, hist = partition_buckets(
        e_keys, e_ties, s_keys, s_ties, n_buckets=nb, count=shard.count)

    # --- 4. histogram psum, greedy contiguous group assignment -------------
    hist = hist.astype(jnp.int64)                                   # (nb,)
    my_prefix, totals = subcube_prefix_sum(hist, axis_name, p, sub_dims)
    total = jnp.sum(totals)
    cum = jnp.cumsum(totals)
    cum_before = cum - totals
    mid = cum_before + totals // 2
    g_of_bucket = jnp.clip((mid * k) // jnp.maximum(total, 1), 0, k - 1)
    group_total = jnp.zeros((k,), jnp.int64).at[g_of_bucket].add(totals)
    cum_grp = jnp.cumsum(group_total) - group_total                # before grp

    # --- 5. per-element target PE (perfect balance within groups) ----------
    q_in_bucket = q_in_bucket.astype(jnp.int64)
    bsafe = jnp.clip(bucket, 0, nb - 1)
    g_e = g_of_bucket[bsafe]
    pos_in_group = (cum_before[bsafe] - cum_grp[g_e]
                    + my_prefix[bsafe] + q_in_bucket)
    gt = jnp.maximum(group_total[g_e], 1)
    t_in_group = (pos_in_group * p_g) // gt
    dest = (g_e * p_g + t_in_group).astype(jnp.int32)
    dest = jnp.where(shard.valid_mask(), dest, p_sub)

    # --- 6. fused slotted all-to-all within the subcube --------------------
    out, ovf = _alltoall_route(shard, dest, axis_name, p_sub,
                               _slot_cap(cap, p_sub, slot_factor),
                               groups=groups, stream=overlap)
    if not overlap:                         # streamed arrives sorted
        out = local_sort(out)
    # restore working capacity
    out, ovf2 = resize(out, cap)
    return out, ovf + ovf2
