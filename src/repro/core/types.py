"""Core element representation for the distributed sorting library.

The paper's algorithms exchange *dynamically sized* MPI messages.  JAX is a
static-shape SPMD system, so every per-PE fragment of the input is held in a
fixed-capacity, ascending-sorted buffer padded with the key-space maximum:

    SortShard(keys=(C,), vals={name: (C,)}, count=())

``count`` is the number of valid elements; ``keys[count:] == PAD``.  The
capacity C is provisioned from the paper's own load guarantees (Lemma 3:
subcube imbalance is O(1) w.h.p. after the initial random shuffle) and every
algorithm returns an ``overflow`` flag that the tests assert to be zero on
all ten adversarial input distributions.

Keys are order-preserving bit-casts of the user dtype into uint32/uint64
(the classic monotone float transform), so all comparisons inside the
library are unsigned-integer comparisons and "+inf padding" is just the
all-ones word.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Order-preserving key transforms
# ---------------------------------------------------------------------------

_UINT_MAX = {jnp.dtype("uint32"): np.uint32(0xFFFFFFFF),
             jnp.dtype("uint64"): np.uint64(0xFFFFFFFFFFFFFFFF)}


def key_to_uint(x: jax.Array) -> jax.Array:
    """Map f32/f64/i32/i64/u32/u64 keys to unsigned ints, order-preserving."""
    dt = x.dtype
    if dt in (jnp.uint32, jnp.uint64):
        return x
    if dt == jnp.int32:
        return (x.view(jnp.uint32) ^ np.uint32(0x80000000)).astype(jnp.uint32)
    if dt == jnp.int64:
        return x.view(jnp.uint64) ^ np.uint64(0x8000000000000000)
    if dt == jnp.float32:
        b = x.view(jnp.uint32)
        # negative floats: flip all bits;  non-negative: flip the sign bit.
        mask = jnp.where(b >> 31 == 1, np.uint32(0xFFFFFFFF), np.uint32(0x80000000))
        return b ^ mask
    if dt == jnp.float64:
        b = x.view(jnp.uint64)
        mask = jnp.where(b >> 63 == 1, np.uint64(0xFFFFFFFFFFFFFFFF),
                         np.uint64(0x8000000000000000))
        return b ^ mask
    raise TypeError(f"unsupported key dtype {dt}")


def uint_to_key(u: jax.Array, orig_dtype) -> jax.Array:
    """Inverse of :func:`key_to_uint`."""
    dt = jnp.dtype(orig_dtype)
    if dt in (jnp.uint32, jnp.uint64):
        return u
    if dt == jnp.int32:
        return (u ^ np.uint32(0x80000000)).view(jnp.int32)
    if dt == jnp.int64:
        return (u ^ np.uint64(0x8000000000000000)).view(jnp.int64)
    if dt == jnp.float32:
        mask = jnp.where(u >> 31 == 1, np.uint32(0x80000000), np.uint32(0xFFFFFFFF))
        return (u ^ mask).view(jnp.float32)
    if dt == jnp.float64:
        mask = jnp.where(u >> 63 == 1, np.uint64(0x8000000000000000),
                         np.uint64(0xFFFFFFFFFFFFFFFF))
        return (u ^ mask).view(jnp.float64)
    raise TypeError(f"unsupported key dtype {dt}")


def pad_value(dtype) -> np.generic:
    return _UINT_MAX[jnp.dtype(dtype)]


# ---------------------------------------------------------------------------
# SortShard
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SortShard:
    """One PE's fixed-capacity fragment.  ``keys`` sorted ascending, padded."""

    keys: jax.Array                      # (C,) uint32/uint64
    vals: Dict[str, jax.Array]           # each (C,) — payload travels along
    count: jax.Array                     # () int32, number of valid entries

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    @property
    def pad(self):
        return pad_value(self.keys.dtype)

    def valid_mask(self) -> jax.Array:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.count

    def replace(self, **kw) -> "SortShard":
        return dataclasses.replace(self, **kw)


def make_shard(keys: jax.Array, count=None, capacity: Optional[int] = None,
               vals: Optional[Dict[str, jax.Array]] = None,
               sort_local: bool = True) -> SortShard:
    """Build a SortShard from raw keys (any supported dtype)."""
    u = key_to_uint(keys)
    n = u.shape[0]
    cap = capacity or n
    if count is None:
        count = jnp.int32(n)
    count = jnp.asarray(count, jnp.int32)
    pad = pad_value(u.dtype)
    idx = jnp.arange(cap, dtype=jnp.int32)
    if cap != n:
        u = jnp.concatenate([u, jnp.full((cap - n,), pad, u.dtype)])
        vals = {k: jnp.concatenate(
                    [v, jnp.zeros((cap - n,) + v.shape[1:], v.dtype)])
                for k, v in (vals or {}).items()}
    vals = dict(vals or {})
    u = jnp.where(idx < count, u, pad)
    shard = SortShard(keys=u, vals=vals, count=count)
    if sort_local:
        shard = local_sort(shard)
    return shard


# Local-phase kernel policy.  Two Pallas kernels cover the local hot spots:
# the bitonic local sort (kernels/bitonic) and the fused partition-into-
# buckets classifier (kernels/partition).  On a TPU backend both default ON
# and compile through Mosaic (``repro.kernels.interpret_mode``); the sort
# kernel takes a shard only up to ``KERNEL_SORT_MAX_WORDS`` (see
# :func:`_pallas_sortable`).  Everywhere else (CPU/sim CI) they default OFF
# because there they run in the Pallas interpreter, which is slow; the jnp
# paths are the bitwise oracle the kernels are diffed against.  The
# ``REPRO_LOCAL_KERNELS`` environment variable (read at trace time, so
# ``monkeypatch.setenv`` works) overrides the default:
#
#   REPRO_LOCAL_KERNELS=all | 1 | on      both kernels
#   REPRO_LOCAL_KERNELS=none | 0 | off    neither
#   REPRO_LOCAL_KERNELS=sort,partition    an explicit subset
#   REPRO_LOCAL_KERNELS=auto              backend default (TPU → both)
#
# The legacy sort-only toggles (``REPRO_PALLAS_LOCAL_SORT`` and
# :func:`set_pallas_local_sort`) still work and override the ``sort``
# component; :func:`set_local_kernels` overrides the whole policy.
_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("", "0", "none", "off", "false", "no")


@dataclasses.dataclass(frozen=True)
class LocalKernelPolicy:
    """Which Pallas local-phase kernels are active.  Frozen/hashable so it
    can key a jit cache (``psort`` passes it as a static argument)."""

    sort: bool = False
    partition: bool = False


_PALLAS_LOCAL_SORT_OVERRIDE: Optional[bool] = None
_LOCAL_KERNELS_OVERRIDE: Optional[LocalKernelPolicy] = None


def _default_local_kernels() -> LocalKernelPolicy:
    on = jax.default_backend() == "tpu"
    return LocalKernelPolicy(sort=on, partition=on)


def _parse_local_kernels(spec: str) -> LocalKernelPolicy:
    s = spec.strip().lower()
    if s == "auto":
        return _default_local_kernels()
    if s in _FALSY:
        return LocalKernelPolicy()
    if s == "all" or s in _TRUTHY:
        return LocalKernelPolicy(sort=True, partition=True)
    parts = {t.strip() for t in s.split(",") if t.strip()}
    unknown = parts - {"sort", "partition"}
    if unknown:
        raise ValueError(f"REPRO_LOCAL_KERNELS: unknown kernel(s) "
                         f"{sorted(unknown)} in {spec!r} (know: sort, "
                         f"partition, all, none, auto)")
    return LocalKernelPolicy(sort="sort" in parts,
                             partition="partition" in parts)


def local_kernels() -> LocalKernelPolicy:
    """The active local-kernel policy: programmatic override
    (:func:`set_local_kernels`) > ``REPRO_LOCAL_KERNELS`` > backend default
    (TPU → both on), with the legacy sort-only toggles layered on the
    ``sort`` component."""
    if _LOCAL_KERNELS_OVERRIDE is not None:
        return _LOCAL_KERNELS_OVERRIDE
    env = os.environ.get("REPRO_LOCAL_KERNELS")
    pol = _parse_local_kernels(env) if env is not None \
        else _default_local_kernels()
    if _PALLAS_LOCAL_SORT_OVERRIDE is not None:
        pol = dataclasses.replace(pol, sort=_PALLAS_LOCAL_SORT_OVERRIDE)
    else:
        legacy = os.environ.get("REPRO_PALLAS_LOCAL_SORT")
        if legacy is not None:
            pol = dataclasses.replace(pol, sort=legacy.lower() in _TRUTHY)
    return pol


def set_local_kernels(policy: Optional[LocalKernelPolicy]
                      ) -> Optional[LocalKernelPolicy]:
    """Force the whole kernel policy (``None`` = defer to the environment /
    backend default again).  Returns the previous override."""
    global _LOCAL_KERNELS_OVERRIDE
    prev = _LOCAL_KERNELS_OVERRIDE
    _LOCAL_KERNELS_OVERRIDE = policy
    return prev


def use_pallas_local_sort() -> bool:
    """Is the Pallas local-sort kernel enabled?  (Back-compat shim for the
    pre-policy spelling: equals ``local_kernels().sort``.)"""
    return local_kernels().sort


def set_pallas_local_sort(enabled: Optional[bool]) -> Optional[bool]:
    """Force the Pallas local-sort path on/off (``None`` = defer to the
    environment variable again).  Returns the previous override so callers
    can restore it."""
    global _PALLAS_LOCAL_SORT_OVERRIDE
    prev = _PALLAS_LOCAL_SORT_OVERRIDE
    _PALLAS_LOCAL_SORT_OVERRIDE = enabled
    return prev


@jax.named_scope("local_sort")
def local_sort(shard: SortShard) -> SortShard:
    """Sort a shard's valid elements ascending; every payload plane travels
    with its key and the pad words follow the valid prefix.

    By default this is one stable XLA sort of (keys, *payloads) on the key:
    equal keys keep their input order, and a real key equal to the pad word
    stays ahead of the pads.  Under the ``sort`` kernel policy (the TPU
    default) the Pallas bitonic network sorts instead where it can: 4-byte
    keys, at most one 1-D 4-byte payload, at most ``KERNEL_SORT_MAX_WORDS``
    words.  It is not stable, so equal keys may come out with their
    payloads in another order."""
    keys = jnp.where(shard.valid_mask(), shard.keys, shard.pad)
    if use_pallas_local_sort() and _pallas_sortable(shard):
        from repro.kernels.bitonic import local_sort_fast
        if not shard.vals:
            return shard.replace(keys=local_sort_fast(keys))
        (vname, vals), = shard.vals.items()
        ks, vs = local_sort_fast(keys, vals)
        return shard.replace(keys=ks, vals={vname: vs})
    # 1-D payloads are operands of the sort itself; a wider one (rows per
    # element) is gathered by the sorted position of a carried iota.
    flat = [k for k, v in shard.vals.items() if v.ndim == 1]
    wide = [k for k in shard.vals if k not in flat]
    carried = [shard.vals[k] for k in flat]
    if wide:
        carried.append(jnp.arange(shard.capacity, dtype=jnp.int32))
    out = jax.lax.sort((keys, *carried), num_keys=1, is_stable=True)
    vals = dict(zip(flat, out[1:]))
    vals.update({k: shard.vals[k][out[-1]] for k in wide})
    return shard.replace(keys=out[0], vals=vals)


# The largest shard the bitonic kernel sorts; above it, XLA's sort.  On one
# TPU v5e, alone, XLA's stable sort of (key, u32 payload) beats the kernel
# at every size measured (median of 25 warm calls: 2^17 words 1.01 ms
# against 1.29; 1,057,292 words 3.46 against 10.2; 2,109,464 words 6.85
# against 21.4; 2^25 words 115.5 against 386.1; tools/local_sort_sweep.py).
# Inside psort the picture differs.  At 2^25 words (one chip, n = 2^24)
# XLA's sort cut the device time of a call from 391 to 121 ms.  But the
# four-chip RAMS program at n = 2^20, whose sorts are 1,057,292 and
# 2,109,464 words, got slower with it: a 90th-percentile call of 152.2 ms
# against 135.8 with the kernel.  So the kernel keeps the sizes it pads to
# 2^22 words or fewer, the largest at which it was measured ahead.
KERNEL_SORT_MAX_WORDS = 1 << 22


def _pallas_sortable(shard: SortShard) -> bool:
    from repro.kernels.bitonic import supported
    if not supported(shard.capacity, shard.keys.dtype):
        return False
    if shard.capacity > KERNEL_SORT_MAX_WORDS:
        return False
    if len(shard.vals) > 1:
        return False
    return all(jnp.dtype(v.dtype).itemsize == 4 and v.ndim == 1
               for v in shard.vals.values())


# ---------------------------------------------------------------------------
# Padded merge of two ascending-sorted shards
# ---------------------------------------------------------------------------


def _take(shard_keys, vals, order):
    return shard_keys[order], {k: v[order] for k, v in vals.items()}


@jax.named_scope("merge_shards")
def merge_shards(a: SortShard, b: SortShard, capacity: Optional[int] = None,
                 tie_a_first: bool = True):
    """Merge two sorted padded shards into one of size ``capacity``.

    Returns (merged, overflow) where overflow counts elements dropped because
    the combined valid count exceeded the capacity.  On ties, elements of
    ``a`` precede elements of ``b`` (the stable "left block first" rule that
    realizes the paper's implicit origin-ordering, cf. RFIS tie-breaking).
    """
    cap = capacity or max(a.capacity, b.capacity)
    total = a.count + b.count
    keys = jnp.concatenate([a.keys, b.keys])
    # Padding must sort *after* any real element of the same (max) key value:
    # give each entry a secondary "is-padding" flag and lexsort.  For the
    # common key-only case a plain sort is sufficient and cheaper only when
    # no payload exists AND keys cannot collide with the pad word; we keep
    # the safe path everywhere (XLA fuses the two sort passes anyway).
    # ``tie_a_first`` may be a traced bool (e.g. bitonic's compare-split
    # needs the *pair-consistent* lower-PE-first order so both partners
    # construct the identical merged sequence).
    apad = ~a.valid_mask()
    bpad = ~b.valid_mask()
    tie_a = jnp.asarray(tie_a_first)
    # tie order: valid a (0) < valid b (1) < padding (2), flipped when !tie_a
    rank_a = jnp.where(apad, jnp.int32(2),
                       jnp.where(tie_a, jnp.int32(0), jnp.int32(1)))
    rank_b = jnp.where(bpad, jnp.int32(2),
                       jnp.where(tie_a, jnp.int32(1), jnp.int32(0)))
    rank_b = jnp.broadcast_to(rank_b, bpad.shape)
    rank_a = jnp.broadcast_to(rank_a, apad.shape)
    tie = jnp.concatenate([rank_a, rank_b])
    order = jnp.lexsort((tie, keys))
    vals = {k: jnp.concatenate([a.vals[k], b.vals[k]]) for k in a.vals}
    mk, mv = _take(keys, vals, order)
    if mk.shape[0] > cap:
        mk = mk[:cap]
        mv = {k: v[:cap] for k, v in mv.items()}
    elif mk.shape[0] < cap:
        pad = pad_value(mk.dtype)
        extra = cap - mk.shape[0]
        mk = jnp.concatenate([mk, jnp.full((extra,), pad, mk.dtype)])
        mv = {k: jnp.concatenate([v, jnp.zeros((extra,) + v.shape[1:], v.dtype)])
              for k, v in mv.items()}
    new_count = jnp.minimum(total, jnp.int32(cap))
    overflow = jnp.maximum(total - jnp.int32(cap), 0)
    # re-pad keys beyond count (dropped elements / stale pads)
    idx = jnp.arange(cap, dtype=jnp.int32)
    mk = jnp.where(idx < new_count, mk, pad_value(mk.dtype))
    return SortShard(keys=mk, vals=mv, count=new_count), overflow


def merge_sorted_shards(a: SortShard, b: SortShard,
                        capacity: Optional[int] = None):
    """Positional merge of two ascending-sorted shards (a-before-b ties).

    Produces the same ``(merged, overflow)`` as
    ``merge_shards(a, b, capacity, tie_a_first=True)`` on everything a
    consumer can observe — keys (the pad region is re-padded), counts,
    overflow, and vals in ``[0, count)`` — but computes each element's
    merged position directly with two ``searchsorted`` passes and scatters,
    instead of lexsorting the concatenation.  That turns the running-merge
    fold of a streamed exchange from O(C log C) per chunk into O(C), which
    is what makes the incremental consumer competitive with the barrier
    path's single post-shuffle sort.

    Vals beyond ``count`` are zeros on the scatter path and leftover pad
    payloads on the sort paths (the lexsort path leaves whatever the
    dropped pad entries carried); no caller reads them.
    """
    cap = capacity or max(a.capacity, b.capacity)
    ca, cb = a.count, b.count
    ma, mb = a.capacity, b.capacity
    total = ca + cb
    new_count = jnp.minimum(total, jnp.int32(cap))
    overflow = jnp.maximum(total - jnp.int32(cap), 0)
    idx = jnp.arange(cap, dtype=jnp.int32)

    # A per-element *rank* that realizes the merge's tie order when compared
    # after the key: valid a (own position) < valid b (ma + position) < pads
    # (ma + mb + concatenation position).  Ranks are unique across the
    # concatenation, so (key, rank) pairs are distinct and any (key, rank)
    # sort — stable or not — reproduces the lexsort permutation exactly.
    ia = jnp.arange(ma, dtype=jnp.int32)
    ib = jnp.arange(mb, dtype=jnp.int32)
    ra = jnp.where(ia < ca, ia, jnp.int32(ma + mb) + ia)
    rb = jnp.where(ib < cb, jnp.int32(ma) + ib,
                   jnp.int32(2 * ma + mb) + ib)

    def finish(mk, mv):
        mk = jnp.where(idx < new_count, mk, pad_value(mk.dtype))
        return SortShard(keys=mk, vals=mv, count=new_count), overflow

    def cut(v):
        m = ma + mb
        if m > cap:
            return v[:cap]
        if m < cap:
            fill = jnp.zeros((cap - m,) + v.shape[1:], v.dtype)
            return jnp.concatenate([v, fill])
        return v

    if not a.vals and a.keys.dtype == jnp.uint32:
        # keys-only u32: one single-operand u64 sort of (key << 32 | rank) —
        # measured at the plain-concat-sort lower bound on CPU, ~3x the
        # searchsorted/scatter formulation below
        comp = jnp.concatenate([
            (a.keys.astype(jnp.uint64) << 32) | ra.astype(jnp.uint64),
            (b.keys.astype(jnp.uint64) << 32) | rb.astype(jnp.uint64)])
        mk = (jnp.sort(comp) >> 32).astype(jnp.uint32)
        return finish(cut(mk), {})

    if all(v.ndim == 1 for v in a.vals.values()):
        # 1-D payloads ride a two-key lax.sort as extra operands
        keys = jnp.concatenate([a.keys, b.keys])
        rank = jnp.concatenate([ra, rb])
        ops = [keys, rank] + [jnp.concatenate([a.vals[k], b.vals[k]])
                              for k in a.vals]
        out = jax.lax.sort(ops, num_keys=2)
        mv = {k: cut(v) for k, v in zip(a.vals, out[2:])}
        return finish(cut(out[0]), mv)

    # general fallback (multi-dim payloads): compute each element's merged
    # position directly and scatter.  Position of a[i] = i + |{valid b
    # strictly less}| ('left' keeps equal-key b after a; b's pads — the
    # key-space max — only tie, never count).  Position of b[j] = j +
    # |{valid a less-or-equal}| ('right' counts equal-key a first; the
    # clamp to ca excludes a's pads when b[j] equals the pad word).
    nb = jnp.minimum(jnp.searchsorted(b.keys, a.keys, side="left"),
                     cb).astype(jnp.int32)
    na = jnp.minimum(jnp.searchsorted(a.keys, b.keys, side="right"),
                     ca).astype(jnp.int32)
    pos_a = jnp.where(ia < ca, ia + nb, jnp.int32(cap))   # cap ⇒ dropped
    pos_b = jnp.where(ib < cb, ib + na, jnp.int32(cap))
    mk = jnp.full((cap,), pad_value(a.keys.dtype), a.keys.dtype)
    mk = mk.at[pos_a].set(a.keys, mode="drop").at[pos_b].set(b.keys,
                                                             mode="drop")
    mv = {}
    for k in a.vals:
        va, vb = a.vals[k], b.vals[k]
        buf = jnp.zeros((cap,) + va.shape[1:], va.dtype)
        mv[k] = buf.at[pos_a].set(va, mode="drop").at[pos_b].set(vb,
                                                                 mode="drop")
    # overflowed elements were scattered at positions >= cap and dropped —
    # exactly the tail the lexsort path truncates
    return finish(mk, mv)


def resize(shard: SortShard, capacity: int):
    """Grow/shrink a shard's buffer (sorted, padded).  Returns (shard, overflow)."""
    if capacity == shard.capacity:
        return shard, jnp.int32(0)
    pad = shard.pad
    if capacity > shard.capacity:
        extra = capacity - shard.capacity
        keys = jnp.concatenate([shard.keys, jnp.full((extra,), pad, shard.keys.dtype)])
        vals = {k: jnp.concatenate([v, jnp.zeros((extra,) + v.shape[1:], v.dtype)])
                for k, v in shard.vals.items()}
        return SortShard(keys, vals, shard.count), jnp.int32(0)
    keys = shard.keys[:capacity]
    vals = {k: v[:capacity] for k, v in shard.vals.items()}
    overflow = jnp.maximum(shard.count - capacity, 0)
    return SortShard(keys, vals, jnp.minimum(shard.count, capacity)), overflow


def compact(shard: SortShard, keep_mask: jax.Array) -> SortShard:
    """Keep only elements where ``keep_mask`` (and valid); re-pack sorted."""
    keep = keep_mask & shard.valid_mask()
    pad = shard.pad
    keys = jnp.where(keep, shard.keys, pad)
    order = jnp.argsort(jnp.where(keep, jnp.int32(0), jnp.int32(1)), stable=True)
    keys = keys[order]
    vals = {k: v[order] for k, v in shard.vals.items()}
    return SortShard(keys, vals, jnp.sum(keep).astype(jnp.int32))
