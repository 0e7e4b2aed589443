"""Hypercube communication patterns on a named mesh axis.

The paper uses the hypercube design pattern (Algorithm 1) for everything:
all-gather-merge, reductions, random shuffling and routing.  On TPU the
pairwise ``i XOR 2^j`` exchange maps 1:1 onto ``jax.lax.ppermute`` with a
static permutation — a single collective-permute over ICI per step, which is
exactly the static-schedule analogue of the paper's point-to-point message.

All functions here must be called *inside* ``shard_map`` over ``axis_name``.
Subcube collectives need no communicator splitting (the paper's complaint
about ``MPI_Comm_Split``): an XOR permutation on bit ``j < dims`` never
leaves the subcube, and grouped collectives use ``axis_index_groups``.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import comm
from .types import SortShard, local_sort, merge_shards, \
    merge_sorted_shards, pad_value, compact, \
    resize


def xor_perm(p: int, j: int):
    return [(i, i ^ (1 << j)) for i in range(p)]


def subcube_groups(p: int, dims: int):
    """PE groups sharing bits ``dims..`` — the 2^dims-sized subcubes."""
    size = 1 << dims
    return [[h * size + l for l in range(size)] for h in range(p // size)]


def hc_exchange(x, axis_name: str, p: int, j: int):
    """Send ``x`` to partner ``i ^ 2^j``; return the partner's ``x``."""
    return comm.ppermute(x, axis_name, xor_perm(p, j))


def exchange_shard(shard: SortShard, axis_name: str, p: int, j: int) -> SortShard:
    return SortShard(
        keys=hc_exchange(shard.keys, axis_name, p, j),
        vals={k: hc_exchange(v, axis_name, p, j) for k, v in shard.vals.items()},
        count=hc_exchange(shard.count, axis_name, p, j),
    )


# ---------------------------------------------------------------------------
# All-gather-merge (paper §II): all PEs end with all elements, sorted.
# ---------------------------------------------------------------------------


def allgather_merge(shard: SortShard, axis_name: str, p: int,
                    dims: Optional[Sequence[int]] = None,
                    tie_by_origin: bool = True) -> SortShard:
    """Recursive-doubling all-gather-merge over hypercube dims (low→high).

    After step t the buffer holds the merged elements of the 2^(t+1)-subcube.
    When ``tie_by_origin`` is set, equal keys are ordered by origin-PE block
    (lower PE numbers first) — the stable-merge realization of the paper's
    implicit (x, origin, i) lexicographic tie-breaking: at every step the two
    blocks cover disjoint, ordered ranges of origin PEs, so putting the block
    of the lower subcube first on ties yields a global (key, origin, i) order
    without communicating origin ids.
    """
    dims = list(dims) if dims is not None else list(range(p.bit_length() - 1))
    me = comm.axis_index(axis_name)
    for t in dims:
        partner = exchange_shard(shard, axis_name, p, t)
        i_am_upper = ((me >> t) & 1) == 1
        cap = shard.capacity + partner.capacity
        # lower-origin block first on ties: if I am the upper PE, the
        # partner's block is the lower one (traced tie flag).
        tie_a = ~i_am_upper if tie_by_origin else True
        shard, _ = merge_shards(shard, partner, capacity=cap,
                                tie_a_first=tie_a)
    return shard


# ---------------------------------------------------------------------------
# Butterfly reductions (sum / custom) within subcubes.
# ---------------------------------------------------------------------------


def butterfly_sum(x, axis_name: str, p: int, dims: Sequence[int]):
    """All-reduce(+) over the subcube spanned by ``dims``."""
    for t in dims:
        x = jax.tree.map(lambda a, b: a + b, x,
                         hc_exchange(x, axis_name, p, t))
    return x


def subcube_psum(x, axis_name: str, p: int, dims: int):
    """psum within 2^dims subcubes via axis_index_groups (fused collective)."""
    return comm.psum(x, axis_name, axis_index_groups=subcube_groups(p, dims))


def subcube_prefix_sum(x, axis_name: str, p: int, dims: Sequence[int]):
    """Exclusive prefix sum over PE order within the subcube (hypercube scan).

    Classic hypercube scan: maintain (prefix, total); at step t exchange the
    running total with the partner; lower half adds nothing to prefix, upper
    half adds the partner's total.
    """
    me = comm.axis_index(axis_name)
    prefix = jax.tree.map(jnp.zeros_like, x)
    total = x
    for t in dims:
        other_total = jax.tree.map(lambda v: hc_exchange(v, axis_name, p, t), total)
        i_am_upper = ((me >> t) & 1).astype(jnp.int32)
        prefix = jax.tree.map(
            lambda pr, ot: pr + jnp.where(i_am_upper == 1, ot, jnp.zeros_like(ot)),
            prefix, other_total)
        total = jax.tree.map(lambda a, b: a + b, total, other_total)
    return prefix, total


# ---------------------------------------------------------------------------
# Randomized shuffling (paper §III-A / App. C)
# ---------------------------------------------------------------------------


def hypercube_shuffle(shard: SortShard, axis_name: str, p: int, seed,
                      dims: Optional[Sequence[int]] = None
                      ) -> Tuple[SortShard, jax.Array]:
    """Random redistribution in O((α+βn/p)·log p): at each dim, split the
    local data into two random halves and send one to the partner.

    Exactly ⌊m/2⌋ elements are sent each step (the paper's "split local data
    in two random halves" refinement for better load balance).  Returns the
    shuffled shard (unsorted!) and an overflow count.
    """
    dims = list(dims) if dims is not None else list(range(p.bit_length() - 1))
    me = comm.axis_index(axis_name)
    overflow = jnp.int32(0)
    cap = shard.capacity
    for t in dims:
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(seed), t), me)
        scores = jax.random.uniform(key, (cap,))
        scores = jnp.where(shard.valid_mask(), scores, jnp.inf)
        # rank elements by score: the ⌊m/2⌋ smallest are sent.
        order = jnp.argsort(scores)
        rank = jnp.zeros((cap,), jnp.int32).at[order].set(
            jnp.arange(cap, dtype=jnp.int32))
        send_mask = rank < (shard.count // 2)
        sent = compact(shard, send_mask)
        kept = compact(shard, ~send_mask)
        recv = exchange_shard(sent, axis_name, p, t)
        shard, ovf = merge_shards(kept, recv, capacity=cap)
        overflow = overflow + ovf
    return shard, overflow


def alltoall_shuffle(shard: SortShard, axis_name: str, p: int, seed,
                     slot_cap: Optional[int] = None,
                     groups=None, stream: bool = False
                     ) -> Tuple[SortShard, jax.Array]:
    """Direct random shuffle via one fused all-to-all (Helman et al. style).

    On TPU an all-to-all is a single hardware-routed collective, so the αp
    startup penalty the paper associates with direct delivery does not apply;
    volume is βn/p.  Slots are Chernoff-provisioned: targets are uniformly
    random, so per-destination counts concentrate around C/p.

    ``stream=True`` pipelines the exchange against the local merge (see
    :func:`_alltoall_route`): the result is then already locally *sorted*.
    """
    cap = shard.capacity
    if slot_cap is None:
        mean = max(1, cap // p)
        slot_cap = int(mean + 4 * np.sqrt(mean) + 8)
    me = comm.axis_index(axis_name)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), me)
    dest = jax.random.randint(key, (cap,), 0, p).astype(jnp.int32)
    dest = jnp.where(shard.valid_mask(), dest, jnp.int32(p))  # pads → nowhere
    return _alltoall_route(shard, dest, axis_name, p, slot_cap, groups,
                           stream=stream)


@jax.named_scope("alltoall_route")
def _alltoall_route(shard: SortShard, dest: jax.Array, axis_name: str, p: int,
                    slot_cap: int, groups=None,
                    stream: bool = False) -> Tuple[SortShard, jax.Array]:
    """Scatter elements to ``dest`` PEs via slotted all-to-all buffers.

    ``dest`` is a per-element target in [0, p) (p = group size when grouped);
    invalid elements must carry dest == p.  Returns (shard, overflow); the
    output shard has capacity p*slot_cap and is *unsorted* on the barrier
    path (``stream=False``).

    ``stream=True`` replaces the barrier all_to_all with
    :func:`comm.alltoall_stream`: each arriving per-source block is locally
    sorted and folded into a running merge while later blocks are still in
    flight, so the returned shard is already **sorted** (callers skip their
    ``local_sort``).  Bitwise-identical to the barrier path followed by
    ``local_sort`` — see :func:`_stream_route_merge` for the argument —
    and ``overflow`` is computed sender-side, identically on both paths.
    """
    pad = shard.pad
    # slot index of each element within its destination bucket, via stable
    # sort-by-destination ranking: O(C log C + p) instead of the (C, p)
    # one-hot cumsum, whose p² blow-up (C itself is Θ(p·slot_cap) after a
    # shuffle) was the memory wall at p = 1024 on the sim backend.  The
    # assignment is identical: stable order ⇒ elements keep their original
    # relative order within a destination bucket.  Each element's bucket
    # start comes from the p + 1 bucket bounds (dest lies in [0, p]): one
    # gather from a (p + 1)-word table.
    cap_in = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    bounds = jnp.searchsorted(sorted_dest, jnp.arange(p + 1, dtype=jnp.int32),
                              side="left")
    first = bounds[sorted_dest]
    rank_in_bucket = jnp.arange(cap_in, dtype=jnp.int32) - first.astype(jnp.int32)
    slot = jnp.zeros((cap_in,), jnp.int32).at[order].set(rank_in_bucket)
    sent_counts = (bounds[1:] - bounds[:-1]).astype(jnp.int32)    # (p,)
    overflow = jnp.sum(jnp.maximum(sent_counts - slot_cap, 0))
    ok = (dest < p) & (slot < slot_cap)
    flat = dest * slot_cap + slot
    flat = jnp.where(ok, flat, p * slot_cap)  # dump dropped/invalid

    def scatter(v, fill):
        trail = v.shape[1:]
        buf = jnp.full((p * slot_cap + 1,) + trail, fill, v.dtype)
        okb = ok.reshape((-1,) + (1,) * len(trail)) if trail else ok
        buf = buf.at[flat].set(jnp.where(okb, v, fill))
        return buf[:-1].reshape((p, slot_cap) + trail)

    keys = scatter(shard.keys, pad)
    vals = {k: scatter(v, np.zeros((), v.dtype)) for k, v in shard.vals.items()}
    counts = jnp.minimum(sent_counts, slot_cap)                   # (p,)

    if stream:
        out = _stream_route_merge(keys, vals, counts, pad, axis_name, p,
                                  slot_cap, groups)
        return out, overflow

    a2a = lambda v: comm.all_to_all(v, axis_name, split_axis=0, concat_axis=0,
                                    axis_index_groups=groups, tiled=True)
    keys = a2a(keys).reshape(-1)
    vals = {k: a2a(v).reshape((p * slot_cap,) + v.shape[2:])
            for k, v in vals.items()}
    counts = a2a(counts.reshape(p, 1)).reshape(-1)
    out = SortShard(keys=keys, vals=vals, count=jnp.sum(counts).astype(jnp.int32))
    # compact: valid = slot < per-source count
    slot_idx = jnp.arange(p * slot_cap, dtype=jnp.int32) % slot_cap
    valid = slot_idx < jnp.repeat(counts, slot_cap, total_repeat_length=p * slot_cap)
    out = compact(out.replace(count=jnp.int32(p * slot_cap)), valid)
    return out, overflow


def _stream_route_merge(keys, vals, counts, pad, axis_name: str, p: int,
                        slot_cap: int, groups) -> SortShard:
    """Incremental-merge consumer of a streamed slotted exchange.

    Each arriving per-source block is locally sorted *while later blocks
    are still in flight* — that is the work the stream hides behind the
    wire — and staged into a per-source run table at row ``src``.  Once the
    stream drains, the ``p`` sorted runs collapse through a balanced k-way
    merge tree (``log2 p`` levels of :func:`merge_sorted_shards`, lower
    source rank on the left), so the consumer does O(C log p) merge work —
    the same asymptotics as the barrier path's single post-shuffle sort —
    instead of the O(C·p) a naive fold-into-one-accumulator would cost.

    Staging by source rank makes the result invariant to the delivery
    interleaving :func:`comm.alltoall_stream` leaves implementation-defined.
    Ties across sources resolve left-run-first through every tree level,
    i.e. globally ascending (source, slot) — exactly the (stable) order the
    barrier path produces via ``compact`` + a full ``local_sort``, so both
    paths are bitwise-identical.
    """
    cap_out = p * slot_cap

    def empty():
        return {
            "keys": jnp.full((p, slot_cap), pad, keys.dtype),
            "vals": {k: jnp.zeros((p, slot_cap) + v.shape[2:], v.dtype)
                     for k, v in vals.items()},
            "counts": jnp.zeros((p,), jnp.int32)}

    def fold(acc, chunk, src):
        ck = SortShard(
            keys=chunk["keys"].reshape(-1),
            vals={k: v.reshape((slot_cap,) + v.shape[2:])
                  for k, v in chunk["vals"].items()},
            count=chunk["counts"].reshape(()).astype(jnp.int32))
        ck = local_sort(ck)           # overlapped with the in-flight blocks
        src = src.astype(jnp.int32)
        acc = dict(acc)
        acc["keys"] = jax.lax.dynamic_update_slice(
            acc["keys"], ck.keys[None], (src, jnp.int32(0)))
        acc["vals"] = {
            k: jax.lax.dynamic_update_slice(
                acc["vals"][k], v[None],
                (src,) + (jnp.int32(0),) * (v.ndim))
            for k, v in ck.vals.items()}
        acc["counts"] = acc["counts"].at[src].set(ck.count)
        return acc

    x = {"keys": keys, "vals": vals, "counts": counts.reshape(p, 1)}
    st = comm.alltoall_stream(x, axis_name, fold, empty(), p,
                              axis_index_groups=groups)

    def pair_merge(a_keys, a_vals, a_count, b_keys, b_vals, b_count):
        a = SortShard(keys=a_keys, vals=a_vals, count=a_count)
        b = SortShard(keys=b_keys, vals=b_vals, count=b_count)
        merged, _ = merge_sorted_shards(
            a, b, capacity=a.capacity + b.capacity)  # never overflows
        return merged.keys, merged.vals, merged.count

    if p & (p - 1) == 0:
        # power-of-two source count: one vmapped pair-merge per tree level
        rk, rv, rc = st["keys"], st["vals"], st["counts"]
        while rk.shape[0] > 1:
            rk, rv, rc = jax.vmap(pair_merge)(
                rk[0::2], {k: v[0::2] for k, v in rv.items()}, rc[0::2],
                rk[1::2], {k: v[1::2] for k, v in rv.items()}, rc[1::2])
        out = SortShard(keys=rk[0], vals={k: v[0] for k, v in rv.items()},
                        count=rc[0])
    else:
        runs = [SortShard(keys=st["keys"][i],
                          vals={k: v[i] for k, v in st["vals"].items()},
                          count=st["counts"][i])
                for i in range(p)]
        while len(runs) > 1:
            nxt = []
            for i in range(0, len(runs) - 1, 2):
                a, b = runs[i], runs[i + 1]
                merged, _ = merge_sorted_shards(
                    a, b, capacity=a.capacity + b.capacity)
                nxt.append(merged)
            if len(runs) % 2:
                nxt.append(runs[-1])
            runs = nxt
        out = runs[0]
    assert out.capacity == cap_out
    return out


# ---------------------------------------------------------------------------
# Hypercube routing by explicit target PE (paper App. B) — used by RFIS
# delivery and GatherM.  Elements carry their target in vals['_tgt'].
# ---------------------------------------------------------------------------


def route_by_target(shard: SortShard, axis_name: str, p: int,
                    dims: Sequence[int], capacity: Optional[int] = None,
                    sorted_merge: bool = True) -> Tuple[SortShard, jax.Array]:
    """Route each element to PE ``vals['_tgt']`` via per-dim exchanges.

    In iteration j an element moves iff its target differs from the current
    PE in bit j (high→low).  O(α log p) startups; per-step volume is bounded
    by the concentration argument of §V for RFIS delivery.
    """
    me = comm.axis_index(axis_name)
    cap = capacity or shard.capacity
    shard, overflow = resize(shard, cap)
    for j in sorted(dims, reverse=True):
        tgt = shard.vals["_tgt"].astype(jnp.int32)
        move = ((tgt ^ me) >> j) & 1 == 1
        sent = compact(shard, move)
        kept = compact(shard, ~move)
        recv = exchange_shard(sent, axis_name, p, j)
        shard, ovf = merge_shards(kept, recv, capacity=cap)
        overflow = overflow + ovf
    return shard, overflow
