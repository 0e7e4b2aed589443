"""Distributed selection & query primitives — the sort-free fast paths.

Most queries against a sorted-data service do not need the full sort:
``top_k``, ``rank_of_key``, ``percentile`` and ``range_query`` only need
*one* order statistic (plus a small extraction), and the paper's own
machinery answers them directly:

  * the §III-B **single-reduction median window** (``core/median.py``,
    generalized to arbitrary rank fractions by
    :func:`repro.core.median.butterfly_rank_window`) seeds splitter
    candidates around the target rank in one ``log p`` butterfly;
  * the **multi-level splitter sketch** of Practical Massively Parallel
    Sorting (arXiv 1410.6754; ``rams.quantile_splitters``) pools
    deterministic stride samples of each PE's active key window into
    refined candidates — one fused ``all_gather`` per round.

Exactness does not rest on either estimator: every round *counts* each
candidate with one fused ``psum`` of per-PE ``searchsorted`` ranks, so a
candidate ``c`` with ``#{x < c} < t <= #{x <= c}`` **is** the rank-``t``
element (duplicates — the Zero / DeterDupl distributions — terminate in
one round this way), and otherwise the counts bracket the answer into a
strictly smaller key interval.  A deterministic 16-point grid over the
active interval guarantees ≥ 4 bits of interval shrink per round, so
``ceil(bits/4)`` static rounds always pin the answer exactly — selection
output is **bitwise equal** to indexing the full-sort oracle, at cost
O(n/p · rounds · log cap  +  coll · (rounds + log p)) with *no*
all-to-all and no data movement.

Queries run against a :class:`ResidentData` — the dataset sharded over p
PEs with each shard locally sorted (built once by :func:`shard_data`) —
and are **batched**: every primitive takes a (B,) vector of query
parameters and answers the whole micro-batch with the same collective
schedule (the continuous-batching frontend in
``repro/launch/sort_serve.py`` rides on this).  Both execution backends
of ``psort`` are supported and bitwise-identical.  Collectives are traced
under ``query:*`` phase tags (:func:`repro.core.comm.tagged`) so counted
traces attribute per-phase launches; :func:`trace_query` counts a query's
collectives without executing a FLOP.

>>> import numpy as np
>>> from repro.core.queries import shard_data, top_k, rank_of_key
>>> data = shard_data(np.array([5, 3, 1, 4, 2, 9, 8, 6], np.int32), p=4)
>>> np.asarray(top_k(data, 3, backend="sim"))
array([6, 8, 9], dtype=int32)
>>> rank_of_key(data, 5, backend="sim")     # (#keys < 5, #keys <= 5)
(np.int64(4), np.int64(5))
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import comm
from .median import butterfly_rank_window
from .rams import quantile_splitters
from .types import SortShard, key_to_uint, pad_value, uint_to_key

GRID = 16       # deterministic interval-grid candidates per round
SKETCH = 16     # pooled stride-sketch candidates per round
WINDOW_K = 16   # butterfly rank-window size (u32 key space only)

QUERY_KINDS = ("sort", "top_k", "rank_of_key", "percentile", "range_query")


def n_rounds(bits: int) -> int:
    """Static refinement rounds: the 16-point grid splits the active
    interval into ≥ 17 parts, so each round resolves ≥ 4 key bits."""
    return -(-bits // 4)


# ---------------------------------------------------------------------------
# Resident data: the sharded, locally-sorted dataset queries run against
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ResidentData:
    """A dataset laid out for repeated queries: (p, cap) unsigned key rows
    (PE-major, exactly ``psort``'s input layout), each row locally sorted
    ascending with the key-space maximum as tail padding, plus per-row
    valid counts.  Local sorting is the one-time ingest cost that makes
    every per-candidate rank a ``searchsorted`` instead of a scan."""

    keys: jax.Array          # (p, cap) uint32/uint64, rows sorted ascending
    counts: jax.Array        # (p,) int32
    n: int
    orig_dtype: np.dtype

    @property
    def p(self) -> int:
        return self.keys.shape[0]

    @property
    def cap(self) -> int:
        return self.keys.shape[1]

    @property
    def bits(self) -> int:
        return jnp.dtype(self.keys.dtype).itemsize * 8


def shard_data(keys, p: int) -> ResidentData:
    """Shard a host array over p PEs and locally sort each shard."""
    keys = jnp.asarray(keys)
    if keys.ndim != 1:
        raise ValueError(f"resident data must be 1-D; got {keys.shape}")
    if p < 1 or p & (p - 1):
        raise ValueError(f"p={p} must be a power of two (hypercube layout)")
    n = keys.shape[0]
    u = key_to_uint(keys)
    per = -(-max(n, 1) // p)
    pad = pad_value(u.dtype)
    flat = jnp.full((p * per,), pad, u.dtype).at[:n].set(u)
    rows = jnp.sort(flat.reshape(p, per), axis=1)
    row_counts = jnp.minimum(jnp.maximum(n - per * jnp.arange(p), 0),
                             per).astype(jnp.int32)
    return ResidentData(rows, row_counts, n, np.dtype(keys.dtype))


# ---------------------------------------------------------------------------
# Per-PE SPMD bodies (collectives via repro.core.comm; backend-agnostic)
# ---------------------------------------------------------------------------


def _local_ranks(row, count, cands):
    """(#row < c, #row <= c) for each candidate, restricted to the valid
    prefix.  ``row`` is sorted with max-valued padding, so clipping the
    searchsorted position to ``count`` is exact even when real keys equal
    the pad word (the count smallest entries are exactly the valid ones)."""
    lt = jnp.minimum(jnp.searchsorted(row, cands, side="left"), count)
    le = jnp.minimum(jnp.searchsorted(row, cands, side="right"), count)
    return lt.astype(jnp.int64), le.astype(jnp.int64)


def _counts_body(axis_name: str):
    """body(row, count, cands (B,)) -> global (n_lt, n_le), each (B,)."""

    def body(row, count, cands):
        with comm.tagged("query:counts"):
            lt, le = _local_ranks(row, count, cands)
            g = comm.psum(jnp.stack([lt, le]), axis_name)
        return g[0], g[1]

    return body


def _sketch_candidates(row, count, lo, hi, axis_name):
    """SKETCH pooled candidates per query from the active key windows.

    Each PE contributes a deterministic stride sketch of its local keys
    inside [lo, hi] (the 1410.6754 sample scheme, as in the external
    lane's run sketches); one fused all_gather pools them and
    ``rams.quantile_splitters`` picks evenly spaced order statistics.
    """
    B = lo.shape[0]
    pad = pad_value(row.dtype)
    a = jnp.minimum(jnp.searchsorted(row, lo, side="left"), count)   # (B,)
    b = jnp.minimum(jnp.searchsorted(row, hi, side="right"), count)
    ln = (b - a).astype(jnp.int64)
    jj = jnp.arange(SKETCH, dtype=jnp.int64)
    pos = a[:, None].astype(jnp.int64) + ((2 * jj[None] + 1) * ln[:, None]) \
        // (2 * SKETCH)
    samp = jnp.take(row, jnp.clip(pos, 0, row.shape[0] - 1))         # (B, S)
    samp = jnp.where(ln[:, None] > 0, samp, pad)   # empty window → invalid
    g = comm.all_gather(samp, axis_name)                             # (p,B,S)
    pooled = jnp.sort(jnp.moveaxis(g, 0, 1).reshape(B, -1), axis=1)
    sk = jax.vmap(lambda s: quantile_splitters(s, SKETCH + 1, invalid=pad)
                  )(pooled)                                          # (B, S)
    sk = jnp.where(sk == pad, lo[:, None], sk)
    return jnp.clip(sk, lo[:, None], hi[:, None])


def _grid_candidates(lo, hi):
    """GRID deterministic probes splitting [lo, hi] into ≥ 17 parts; when
    the interval is narrower than the grid the probes enumerate it
    exhaustively (min(j·max(step,1), span)), so narrow intervals resolve
    in one round."""
    udt = lo.dtype
    span = hi - lo                                        # (B,) unsigned
    step = span // np.asarray(GRID + 1).astype(udt)
    j = jnp.arange(1, GRID + 1, dtype=udt)
    off = jnp.minimum(j[None] * jnp.maximum(step, np.asarray(1).astype(udt)
                                            )[:, None], span[:, None])
    return lo[:, None] + off                              # (B, GRID)


_LO64 = np.uint64(0)
_HI64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def _window_candidates(row, count, fracs, axis_name, p):
    """Round-0 candidates from the §III-B butterfly rank window (u32 key
    space only — the lifted u64 window has no headroom above u64 keys).
    Fillers (±inf) map to 0: a harmless duplicate probe, never a wrong
    answer — the counting round decides."""
    dims = list(range(p.bit_length() - 1))
    sh = SortShard(keys=row, vals={}, count=count)
    with comm.tagged("query:window"):
        w = butterfly_rank_window(sh, axis_name, p, dims, WINDOW_K, fracs)
    filler = (w == _LO64) | (w == _HI64)
    return jnp.where(filler, np.uint64(1), w).astype(jnp.uint32) - \
        jnp.where(filler, np.uint32(0), np.uint32(1))


def _select_body(axis_name: str, p: int, bits: int, use_window: bool):
    """body(row, count, ranks (B,) int64 1-indexed, fracs (B,) f64)
    -> (ans (B,) unsigned, n_lt (B,), n_le (B,)) — exact global order
    statistics, identical on every PE."""
    R = n_rounds(bits)

    def body(row, count, ranks, fracs):
        B = ranks.shape[0]
        udt = row.dtype
        umax = pad_value(udt)
        lo = jnp.zeros((B,), udt)
        hi = jnp.full((B,), umax, udt)
        done = jnp.zeros((B,), bool)
        ans = jnp.zeros((B,), udt)
        wc = _window_candidates(row, count, fracs, axis_name, p) \
            if use_window else None
        t = ranks[:, None]
        for r in range(R):
            with comm.tagged(f"query:round{r}"):
                parts = [_grid_candidates(lo, hi),
                         _sketch_candidates(row, count, lo, hi, axis_name)]
                if r == 0 and wc is not None:
                    parts.append(wc)
                cands = jnp.concatenate(parts, axis=1)          # (B, nb)
                lt, le = _local_ranks(row, count, cands)
                g = comm.psum(jnp.stack([lt, le]), axis_name)
            glt, gle = g[0], g[1]
            # a candidate straddling the rank IS the answer (all straddling
            # candidates share one value — counts separate distinct keys)
            hit = (glt < t) & (t <= gle)
            anyhit = jnp.any(hit, axis=1)
            cand_ans = jnp.max(jnp.where(hit, cands, jnp.zeros((), udt)),
                               axis=1)
            # otherwise every candidate brackets: gle < t ⇒ answer > c,
            # glt >= t ⇒ answer < c (c=0 / c=umax can never fire these)
            lo_new = jnp.max(jnp.where(gle < t, cands + np.asarray(1, udt),
                                       lo[:, None]), axis=1)
            hi_new = jnp.min(jnp.where(glt >= t, cands - np.asarray(1, udt),
                                       hi[:, None]), axis=1)
            upd = ~done
            ans = jnp.where(upd & anyhit, cand_ans, ans)
            done = done | (upd & anyhit)
            lo = jnp.where(done, lo, jnp.maximum(lo, lo_new))
            hi = jnp.where(done, hi, jnp.minimum(hi, hi_new))
            pinched = ~done & (lo >= hi)
            ans = jnp.where(pinched, lo, ans)
            done = done | pinched
        ans = jnp.where(done, ans, lo)
        with comm.tagged("query:verify"):
            lt, le = _local_ranks(row, count, ans)
            g = comm.psum(jnp.stack([lt, le]), axis_name)
        return ans, g[0], g[1]

    return body


def _extract_gt(row, count, theta, k_cap: int):
    """Per-PE tail segment of elements strictly above theta (B,) — at most
    k_cap each, since globally fewer than k exceed the rank-(n-k+1) key."""
    pad = pad_value(row.dtype)
    s = jnp.minimum(jnp.searchsorted(row, theta, side="right"), count)
    ln = (count - s).astype(jnp.int32)                       # (B,)
    jj = jnp.arange(k_cap, dtype=jnp.int32)
    pos = jnp.clip(s[:, None] + jj[None], 0, row.shape[0] - 1)
    vals = jnp.take(row, pos)                                # (B, k_cap)
    vals = jnp.where(jj[None] < ln[:, None], vals, pad)
    return vals, ln


def _topk_body(axis_name: str, p: int, bits: int, use_window: bool,
               k_cap: int):
    sel = _select_body(axis_name, p, bits, use_window)

    def body(row, count, ranks, fracs):
        ans, glt, gle = sel(row, count, ranks, fracs)
        vals, ln = _extract_gt(row, count, ans, k_cap)
        return ans, glt, gle, vals, ln

    return body


# ---------------------------------------------------------------------------
# Backend runners (sim = vmapped PEs, shard_map = real devices) + jit caches
# ---------------------------------------------------------------------------

BACKENDS = ("sim", "shard_map")


def _tile(x, p):
    return jnp.broadcast_to(x, (p,) + x.shape)


@partial(jax.jit, static_argnames=("axis", "p"))
def _counts_sim_jit(keys2d, counts, cands, axis, p):
    body = _counts_body(axis)
    return comm.sim_map(body, axis, p)(keys2d, counts, _tile(cands, p))


@partial(jax.jit, static_argnames=("axis", "p", "mesh"))
def _counts_shard_jit(keys2d, counts, cands, mesh, axis, p):
    body = _counts_body(axis)

    def blk(k, c, q):
        out = body(k[0], c[0], q[0])
        return tuple(o[None] for o in out)

    return jax.shard_map(blk, mesh=mesh, in_specs=(P(axis),) * 3,
                         out_specs=(P(axis),) * 2,
                         check_vma=False)(keys2d, counts, _tile(cands, p))


@partial(jax.jit, static_argnames=("axis", "p", "bits", "use_window"))
def _select_sim_jit(keys2d, counts, ranks, fracs, axis, p, bits, use_window):
    body = _select_body(axis, p, bits, use_window)
    return comm.sim_map(body, axis, p)(keys2d, counts, _tile(ranks, p),
                                       _tile(fracs, p))


@partial(jax.jit, static_argnames=("axis", "p", "bits", "use_window", "mesh"))
def _select_shard_jit(keys2d, counts, ranks, fracs, mesh, axis, p, bits,
                      use_window):
    body = _select_body(axis, p, bits, use_window)

    def blk(k, c, r, f):
        out = body(k[0], c[0], r[0], f[0])
        return tuple(o[None] for o in out)

    return jax.shard_map(blk, mesh=mesh, in_specs=(P(axis),) * 4,
                         out_specs=(P(axis),) * 3,
                         check_vma=False)(keys2d, counts, _tile(ranks, p),
                                          _tile(fracs, p))


@partial(jax.jit, static_argnames=("axis", "p", "bits", "use_window",
                                   "k_cap"))
def _topk_sim_jit(keys2d, counts, ranks, fracs, axis, p, bits, use_window,
                  k_cap):
    body = _topk_body(axis, p, bits, use_window, k_cap)
    return comm.sim_map(body, axis, p)(keys2d, counts, _tile(ranks, p),
                                       _tile(fracs, p))


@partial(jax.jit, static_argnames=("axis", "p", "bits", "use_window",
                                   "k_cap", "mesh"))
def _topk_shard_jit(keys2d, counts, ranks, fracs, mesh, axis, p, bits,
                    use_window, k_cap):
    body = _topk_body(axis, p, bits, use_window, k_cap)

    def blk(k, c, r, f):
        out = body(k[0], c[0], r[0], f[0])
        return tuple(o[None] for o in out)

    return jax.shard_map(blk, mesh=mesh, in_specs=(P(axis),) * 4,
                         out_specs=(P(axis),) * 5,
                         check_vma=False)(keys2d, counts, _tile(ranks, p),
                                          _tile(fracs, p))


def _mesh_for(data: ResidentData, mesh, axis: str):
    if mesh is not None:
        return mesh
    from .api import default_mesh
    return default_mesh(data.p, axis)


def _check_backend(backend: str):
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")


# ---------------------------------------------------------------------------
# Host-level query API
# ---------------------------------------------------------------------------


def _as_batch(x, dtype=None):
    a = np.asarray(x) if dtype is None else np.asarray(x, dtype)
    scalar = a.ndim == 0
    return np.atleast_1d(a), scalar


def select_rank(data: ResidentData, ranks, *, backend: str = "sim",
                axis: str = "sort", mesh=None, window: bool = True):
    """Exact keys of the given global ranks (1-indexed, ascending order).

    Returns ``(values, n_lt, n_le)`` where ``values[b]`` is bitwise equal
    to ``np.sort(keys)[ranks[b] - 1]`` and the counts are the number of
    elements strictly below / at-or-below it.
    """
    _check_backend(backend)
    ranks_np, scalar = _as_batch(ranks, np.int64)
    if data.n < 1:
        raise ValueError("select_rank on empty resident data")
    if (ranks_np < 1).any() or (ranks_np > data.n).any():
        raise ValueError(f"ranks must lie in [1, n={data.n}]; got {ranks_np}")
    fracs = (ranks_np - 1) / max(data.n - 1, 1)
    use_window = window and data.bits == 32 and data.p > 1
    if backend == "sim":
        ans, glt, gle = _select_sim_jit(
            data.keys, data.counts, jnp.asarray(ranks_np), jnp.asarray(fracs),
            axis, data.p, data.bits, use_window)
    else:
        mesh = _mesh_for(data, mesh, axis)
        ans, glt, gle = _select_shard_jit(
            data.keys, data.counts, jnp.asarray(ranks_np), jnp.asarray(fracs),
            mesh, axis, data.p, data.bits, use_window)
    ans = np.asarray(uint_to_key(ans[0], data.orig_dtype))
    glt, gle = np.asarray(glt[0]), np.asarray(gle[0])
    if scalar:
        return ans[0], glt[0], gle[0]
    return ans, glt, gle


def rank_of_key(data: ResidentData, keys, *, backend: str = "sim",
                axis: str = "sort", mesh=None):
    """Global ranks of the given key values (batched).

    Returns ``(n_lt, n_le)``: the number of resident elements strictly
    below / at-or-below each query key — i.e. ``np.searchsorted(sorted,
    key, "left")`` and ``..."right"`` of the full-sort oracle.
    """
    _check_backend(backend)
    k_np, scalar = _as_batch(keys, data.orig_dtype)
    u = key_to_uint(jnp.asarray(k_np))
    if backend == "sim":
        glt, gle = _counts_sim_jit(data.keys, data.counts, u, axis, data.p)
    else:
        mesh = _mesh_for(data, mesh, axis)
        glt, gle = _counts_shard_jit(data.keys, data.counts, u, mesh, axis,
                                     data.p)
    glt, gle = np.asarray(glt[0]), np.asarray(gle[0])
    if scalar:
        return glt[0], gle[0]
    return glt, gle


def percentile(data: ResidentData, q, *, backend: str = "sim",
               axis: str = "sort", mesh=None):
    """Exact percentile values (NumPy ``interpolation="lower"``): the
    element at sorted index ``floor(q/100 · (n-1))`` — never interpolated,
    so integer keys stay exact and the answer is bitwise equal to the
    full-sort oracle's."""
    q_np, scalar = _as_batch(q, np.float64)
    if (q_np < 0).any() or (q_np > 100).any():
        raise ValueError(f"percentiles must lie in [0, 100]; got {q_np}")
    ranks = np.floor(q_np / 100.0 * (data.n - 1)).astype(np.int64) + 1
    vals, _, _ = select_rank(data, ranks, backend=backend, axis=axis,
                             mesh=mesh)
    return vals[0] if scalar else vals


def top_k(data: ResidentData, k, *, backend: str = "sim",
          axis: str = "sort", mesh=None):
    """The k largest resident keys, ascending — bitwise equal to
    ``np.sort(keys)[-k:]``.

    One exact rank selection finds the threshold θ = rank n-k+1; each PE
    then contributes its (sorted, ≤ k long) tail of elements > θ, and the
    host closes the multiset with the deficit copies of θ itself (the
    tie-completion that makes the answer exact under duplicates).  With a
    (B,)-batch of k values returns a list of arrays.
    """
    _check_backend(backend)
    k_np, scalar = _as_batch(k, np.int64)
    if (k_np < 1).any() or (k_np > data.n).any():
        raise ValueError(f"k must lie in [1, n={data.n}]; got {k_np}")
    ranks = data.n - k_np + 1
    fracs = (ranks - 1) / max(data.n - 1, 1)
    k_cap = int(min(data.cap, k_np.max()))
    use_window = data.bits == 32 and data.p > 1
    if backend == "sim":
        ans, glt, gle, vals, ln = _topk_sim_jit(
            data.keys, data.counts, jnp.asarray(ranks), jnp.asarray(fracs),
            axis, data.p, data.bits, use_window, k_cap)
    else:
        mesh = _mesh_for(data, mesh, axis)
        ans, glt, gle, vals, ln = _topk_shard_jit(
            data.keys, data.counts, jnp.asarray(ranks), jnp.asarray(fracs),
            mesh, axis, data.p, data.bits, use_window, k_cap)
    theta = np.asarray(ans[0])                       # (B,) unsigned
    gle = np.asarray(gle[0])
    vals = np.asarray(vals)                          # (p, B, k_cap)
    ln = np.asarray(ln)                              # (p, B)
    outs = []
    for b in range(len(k_np)):
        above = np.concatenate([vals[pe, b, :ln[pe, b]]
                                for pe in range(data.p)])
        n_gt = data.n - gle[b]
        assert len(above) == n_gt, (len(above), n_gt)
        full = np.concatenate([np.full(k_np[b] - n_gt, theta[b],
                                       dtype=theta.dtype), above])
        outs.append(np.asarray(uint_to_key(jnp.asarray(np.sort(full)),
                                           data.orig_dtype)))
    return outs[0] if scalar else outs


def range_query(data: ResidentData, lo, hi, *, backend: str = "sim",
                axis: str = "sort", mesh=None):
    """Number of resident keys in the half-open interval [lo, hi) — equal
    to the oracle's ``searchsorted(sorted, hi, "left") -
    searchsorted(sorted, lo, "left")`` (0 when hi <= lo)."""
    _check_backend(backend)
    lo_np, scalar = _as_batch(lo, data.orig_dtype)
    hi_np, _ = _as_batch(hi, data.orig_dtype)
    if lo_np.shape != hi_np.shape:
        raise ValueError(f"lo/hi shape mismatch: {lo_np.shape} vs "
                         f"{hi_np.shape}")
    both = key_to_uint(jnp.concatenate([jnp.asarray(lo_np),
                                        jnp.asarray(hi_np)]))
    if backend == "sim":
        glt, _ = _counts_sim_jit(data.keys, data.counts, both, axis, data.p)
    else:
        mesh = _mesh_for(data, mesh, axis)
        glt, _ = _counts_shard_jit(data.keys, data.counts, both, mesh, axis,
                                   data.p)
    glt = np.asarray(glt[0])
    b = len(lo_np)
    cnt = np.maximum(glt[b:] - glt[:b], 0)
    return cnt[0] if scalar else cnt


# ---------------------------------------------------------------------------
# Counted traces (the measured counterpart of the cost model's query terms)
# ---------------------------------------------------------------------------


def trace_query(kind: str, n: int, p: int, *, batch: int = 1,
                dtype=np.uint32, k: Optional[int] = None) -> comm.CommTrace:
    """Count the collectives one batched query would launch, per PE.

    Like :func:`repro.core.api.trace_collectives` but for the selection
    fast paths: abstractly evaluates the per-PE query body (shapes only,
    no FLOPs) under a :class:`repro.core.comm.CountingCollectives`
    decorator.  Deterministic — EXPERIMENTS.md's mixed-query grid is
    generated from these.  ``kind="sort"`` delegates to the full-sort
    trace for comparison.

    >>> t = trace_query("rank_of_key", 1024, 8, batch=4)
    >>> t.summary()["counts"]
    {'psum': 1}
    >>> t.tags()
    ['query:counts']
    """
    if kind not in QUERY_KINDS:
        raise ValueError(f"unknown query kind {kind!r}; know {QUERY_KINDS}")
    if p < 1 or p & (p - 1):
        raise ValueError(f"p={p} must be a power of two")
    if kind == "sort":
        from .api import SortConfig, trace_collectives
        return trace_collectives(n, SortConfig(p=p))
    bits = np.dtype(dtype).itemsize * 8
    per = -(-max(n, 1) // p)
    use_window = bits == 32 and p > 1
    udt = jnp.uint32 if bits == 32 else jnp.uint64
    counter = comm.CountingCollectives(comm.SIM)
    if kind == "rank_of_key" or kind == "range_query":
        nc = batch if kind == "rank_of_key" else 2 * batch
        body = _counts_body("sort")
        args = (jax.ShapeDtypeStruct((p, per), udt),
                jax.ShapeDtypeStruct((p,), jnp.int32),
                jax.ShapeDtypeStruct((p, nc), udt))
    else:
        k_cap = int(min(per * p, k if k is not None else 16, per * p))
        if kind == "top_k":
            body = _topk_body("sort", p, bits, use_window, max(1, k_cap))
        else:
            body = _select_body("sort", p, bits, use_window)
        args = (jax.ShapeDtypeStruct((p, per), udt),
                jax.ShapeDtypeStruct((p,), jnp.int32),
                jax.ShapeDtypeStruct((p, batch), jnp.int64),
                jax.ShapeDtypeStruct((p, batch), jnp.float64))
    runner = comm.sim_map(body, "sort", p, impl=counter)
    jax.eval_shape(runner, *args)
    return counter.trace
