"""Out-of-core external sorting: shards larger than device memory.

The paper's claim is robustness across 9 orders of magnitude of n/p, but
in-core ``psort`` caps n/p at device memory.  This module lifts the cap
with the classic run-formation + k-way-merge structure of *Scalable
Distributed-Memory External Sorting* (arXiv 0910.2582), mapped onto the
existing four-layer stack:

  Pass A — run formation.  Each PE's oversized shard lives in **host**
    memory and streams through the device in chunks of ``budget``
    elements: copy-in (``jax.device_put``, double-buffered so chunk r+1
    is in flight while chunk r sorts), device sort by the external total
    order (key, tie), copy-out.  The host owns the run buffers; the
    device only ever holds O(budget) elements.
  Pass B — splitter fit.  The distributed phase runs unchanged on
    *splitter summaries*: each sorted run contributes an every-g-th
    element quantile sketch, one fused ``all_gather`` pools the sketches,
    and the RAMS splitter machinery (``rams.quantile_splitters``) picks
    the p-1 global splitters.  Sketches are tiny, so this is the only
    whole-cohort collective.
  Pass C — per-run exchange.  R = ceil(per/budget) all_to_all passes move
    run *slices* instead of whole shards: pass r classifies run r against
    the global splitters (the ``kernels/kway`` classifier when the local
    kernel policy enables it, a jnp lex compare otherwise) and routes
    through the same slotted ``_alltoall_route`` the in-core algorithms
    use.  Slot capacity is **provisioned from the sketches**: a splitter
    interval holding q of a run's sketch points holds at most (q+2)·g of
    the run's elements (the run-slice capacity invariant, proved in
    docs/ARCHITECTURE.md), so the static slots never overflow.
  Pass D — k-way merge.  Each PE merges its R received (sorted) slices:
    the classifier engine cuts the runs at internal splitters fitted from
    pooled run sketches, streams budget-sized chunks through the device
    sort, and concatenates — chunk intervals are disjoint and ordered, so
    the concatenation is sorted.  A loser-tree host merge
    (``merge="losertree"``) is the reference engine the classifier is
    differential-tested against.

Total order: (key, tie) with tie = ``_mix32(global_index)`` — bijective,
so every element is distinct and duplicate-heavy inputs (Zero, DeterDupl)
split evenly across splitter intervals, exactly the RAMS tie-breaking
argument.  The final key output is tie-independent: it is *the* globally
sorted array, hence bitwise-equal to the in-core path for every
algorithm.

u32 keys ride a u64 composite ``(key << 32) | tie`` through
``SortShard``/``local_sort`` (kernel-policy aware); u64 keys keep
separate (key, tie) planes and sort via ``lexsort`` — the composite would
need 96 bits.

Collectives go through the ambient ``comm`` dispatchers, so
``CountingCollectives`` attributes every pass (tags ``ext:runs``,
``ext:splitters``, ``ext:pass{r}``, ``ext:merge``) and
``FaultyCollectives`` can kill/delay any of them; host↔device copies are
recorded as injected ``ext:h2d`` / ``ext:d2h`` pseudo-events
(:meth:`CommTrace.io_bytes`).
"""
from __future__ import annotations

import dataclasses
import heapq
import math
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import comm
from .hypercube import _alltoall_route
from .rams import _mix32, quantile_splitters
from .types import SortShard, local_sort, pad_value

_HI32 = np.uint32(0xFFFFFFFF)
_HI64 = np.uint64(0xFFFFFFFFFFFFFFFF)


@dataclasses.dataclass(frozen=True)
class ExternalPolicy:
    """Out-of-core streaming policy for ``psort(..., external=...)``.

    ``budget`` is the device-resident element budget per PE buffer: shards
    with n/p > budget stream through the device in ceil(n/p / budget)
    runs.  ``sketch_per_run`` sizes the per-run quantile sketch (splitter
    accuracy and exchange-slot provisioning both scale with it).
    ``merge`` picks the pass-D engine: ``"classifier"`` (the kernels/kway
    splitter engine, device-streamed) or ``"losertree"`` (host tournament
    merge — the reference the classifier is tested against).
    ``double_buffer`` overlaps copy-in of chunk r+1 with the device sort
    of chunk r.  ``slot_factor`` scales the sketch-provisioned exchange
    slots (1.0 = the proven bound).
    """

    budget: int
    sketch_per_run: int = 32
    double_buffer: bool = True
    merge: str = "classifier"
    slot_factor: float = 1.0

    def __post_init__(self):
        if self.budget < 1:
            raise ValueError(f"ExternalPolicy.budget must be >= 1, got "
                             f"{self.budget}")
        if self.merge not in ("classifier", "losertree"):
            raise ValueError(f"ExternalPolicy.merge must be 'classifier' or "
                             f"'losertree', got {self.merge!r}")
        if self.sketch_per_run < 1:
            raise ValueError("ExternalPolicy.sketch_per_run must be >= 1")


# ---------------------------------------------------------------------------
# device helpers (module-level jits: cache keyed on (dtype, cap))
# ---------------------------------------------------------------------------


def _sort_planes(k, i, count, *, cap: int):
    """Sort a padded (key, idx) chunk by the external (key, tie) order.

    Returns the (key, tie, idx) planes with the invalid tail at
    (HI, HI32).  The tie plane is derived (``_mix32(idx)``) — it is
    returned so host code never re-implements the mix.  u32 keys route
    the u64 composite through :func:`local_sort` (the kernel policy's
    entry point; the composite is 8 bytes so today's 4-byte bitonic
    kernel declines and the jnp path runs — policy-independent, hence
    safe to cache at module level); u64 keys lexsort their planes.
    """
    pos = jnp.arange(cap, dtype=jnp.int32)
    valid = pos < count
    tie = jnp.where(valid, _mix32(i), _HI32)
    if k.dtype == jnp.uint32:
        c = (k.astype(jnp.uint64) << np.uint64(32)) | tie.astype(jnp.uint64)
        shard = SortShard(keys=jnp.where(valid, c, _HI64),
                          vals={"idx": i}, count=count.astype(jnp.int32))
        shard = local_sort(shard)
        ck = shard.keys
        return ((ck >> np.uint64(32)).astype(jnp.uint32),
                ck.astype(jnp.uint32), shard.vals["idx"])
    km = jnp.where(valid, k, _HI64)
    perm = jnp.lexsort((tie, km))
    return km[perm], tie[perm], i[perm]


# donated (key, idx) buffers: run formation streams budget-sized chunks
# through this, so the device never holds more than the in-flight pair
_device_sort = partial(jax.jit, static_argnames=("cap",),
                       donate_argnums=(0, 1))(_sort_planes)


def _classify_planes(k, t, s_keys, s_ties, nb: int, *, use_kernel: bool):
    """bucket = #splitters lexicographically <= (k, t), in [0, nb-1].

    The kway Pallas kernel runs when the policy enables it, the planes
    are u32, and the block is big enough; otherwise a jnp broadcast lex
    compare (the in-graph fallback — the numpy oracle in kway/ref.py is
    not traceable).  The fallback materializes an (nb-1, C) bool, fine at
    the small splitter counts the external lane uses.
    """
    from repro.kernels.kway import ops as kway_ops
    C = k.shape[0]
    if (use_kernel and k.dtype == jnp.uint32 and t.dtype == jnp.uint32
            and C >= kway_ops._BLOCK and nb >= 2):
        bucket, _ = kway_ops.kway_classify(k, t, s_keys, s_ties,
                                           n_buckets=nb, use_kernel=True)
        return bucket.astype(jnp.int32)
    if s_keys.shape[0] == 0:
        return jnp.zeros((C,), jnp.int32)
    le = ((s_keys[:, None] < k[None, :])
          | ((s_keys[:, None] == k[None, :]) & (s_ties[:, None] <= t[None, :])))
    return jnp.sum(le, axis=0).astype(jnp.int32)


@partial(jax.jit, static_argnames=("nb", "use_kernel"))
def _classify_jit(k, t, count, s_keys, s_ties, *, nb: int, use_kernel: bool):
    """Standalone classify with count masking (invalid tail → nb)."""
    bucket = _classify_planes(k, t, s_keys, s_ties, nb, use_kernel=use_kernel)
    return jnp.where(jnp.arange(k.shape[0]) < count, bucket, nb)


# ---------------------------------------------------------------------------
# host-side mirrors (numpy — sketch provisioning and the loser-tree ref)
# ---------------------------------------------------------------------------


def np_bucket(k, t, s_keys, s_ties):
    """Host mirror of :func:`_classify_planes` (lex splitter count)."""
    k, t = np.asarray(k), np.asarray(t)
    s_keys, s_ties = np.asarray(s_keys), np.asarray(s_ties)
    if s_keys.shape[0] == 0:
        return np.zeros(k.shape[0], np.int64)
    le = ((s_keys[:, None] < k[None, :])
          | ((s_keys[:, None] == k[None, :]) & (s_ties[:, None] <= t[None, :])))
    return le.sum(axis=0)


def run_sketch(k, t, s: int):
    """Every-g-th-element quantile sketch of one sorted run.

    g = ceil(L/s), sketch = run[g-1::g] (at most s points; empty run →
    empty sketch).  Returns (sketch_keys, sketch_ties, g).
    """
    k, t = np.asarray(k), np.asarray(t)
    L = k.shape[0]
    g = max(1, -(-L // s))
    return k[g - 1::g], t[g - 1::g], g


def provision(sketch_k, sketch_t, g: int, s_keys, s_ties, nb: int):
    """Per-interval element bound for one run, from its sketch.

    A splitter interval containing q of the run's stride-g sketch points
    contains at most (q+2)·g of the run's elements: a contiguous index
    range with q stride-g points has length <= (q+1)·g - 1 (the run-slice
    capacity invariant).  Returns an (nb,) int array of bounds.
    """
    q = np.zeros(nb, np.int64)
    if len(sketch_k):
        b = np_bucket(sketch_k, sketch_t, s_keys, s_ties)
        np.add.at(q, np.clip(b, 0, nb - 1), 1)
    return (q + 2) * g


def form_runs(keys, idx, *, budget: int, double_buffer: bool = True,
              io=None) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Pass A for one PE: chunk a host-resident shard into sorted runs.

    ``keys``/``idx`` are host arrays of the PE's valid elements (any
    length, including 0 and non-multiples of ``budget``).  Returns
    ``max(1, ceil(len/budget))`` runs of (key, tie, idx) numpy triples,
    each sorted by (key, tie), concatenation a permutation of the input
    (the chunking round-trip property).  ``io(direction, nbytes)`` is
    called around every host↔device copy; with ``double_buffer`` the
    copy-in of chunk r+1 is issued before chunk r's sort is consumed.
    """
    keys, idx = np.asarray(keys), np.asarray(idx)
    n = keys.shape[0]
    B = int(budget)
    R = max(1, -(-n // B))
    note = io if io is not None else (lambda direction, nbytes: None)

    def _put(r):
        lo, hi = r * B, min((r + 1) * B, n)
        kc = np.full(B, pad_value(keys.dtype), keys.dtype)
        ic = np.zeros(B, np.uint32)
        kc[:hi - lo] = keys[lo:hi]
        ic[:hi - lo] = idx[lo:hi]
        note("ext:h2d", kc.nbytes + ic.nbytes)
        return jax.device_put(kc), jax.device_put(ic), hi - lo

    runs = []
    nxt = _put(0)
    for r in range(R):
        kd, id_, cnt = nxt
        if double_buffer and r + 1 < R:
            nxt = _put(r + 1)          # in flight while chunk r sorts
        ks, ts, is_ = _device_sort(kd, id_, jnp.int32(cnt), cap=B)
        ks, ts, is_ = (np.asarray(ks)[:cnt], np.asarray(ts)[:cnt],
                       np.asarray(is_)[:cnt])
        note("ext:d2h", ks.nbytes + ts.nbytes + is_.nbytes)
        runs.append((ks, ts, is_))
        if not double_buffer and r + 1 < R:
            nxt = _put(r + 1)
    return runs


def _losertree_merge(runs):
    """Host k-way tournament merge (binary-heap loser tree) — the
    reference engine ``merge="classifier"`` is differential-tested
    against."""
    kd, td, id_ = runs[0][0].dtype, runs[0][1].dtype, runs[0][2].dtype
    out = list(heapq.merge(*[zip(k.tolist(), t.tolist(), i.tolist())
                             for k, t, i in runs]))
    if not out:
        return (np.zeros(0, kd), np.zeros(0, td), np.zeros(0, id_))
    k, t, i = zip(*out)
    return (np.asarray(k, kd), np.asarray(t, td), np.asarray(i, id_))


def _pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def merge_runs(runs, *, budget: int, merge: str = "classifier",
               sketch_per_run: int = 32, use_kernel: Optional[bool] = None,
               io=None) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pass D for one PE: k-way merge of sorted (key, tie, idx) runs.

    ``"classifier"`` fits ceil(total/budget) - 1 internal splitters from
    the pooled run sketches, cuts every run at them (device classify —
    the kway kernel when the policy allows), and streams the resulting
    interval chunks through the device sort; the chunks are disjoint
    ordered intervals, so their concatenation is the sorted whole.
    ``"losertree"`` merges on the host.  Equal to a lexsort of the
    concatenation either way (the merge property test).
    """
    runs = [r for r in runs if r[0].shape[0]]
    if not runs:
        return (np.zeros(0, np.uint64), np.zeros(0, np.uint32),
                np.zeros(0, np.uint32))
    if merge == "losertree":
        return _losertree_merge(runs)
    if use_kernel is None:
        from .types import local_kernels
        use_kernel = local_kernels().partition
    note = io if io is not None else (lambda direction, nbytes: None)
    total = sum(r[0].shape[0] for r in runs)
    m = max(1, -(-total // int(budget)))
    if len(runs) == 1:
        return runs[0]

    # internal splitters from the pooled sketches (host-side quantiles —
    # an independent schedule, no bitwise constraint with pass B)
    pk = np.concatenate([run_sketch(k, t, sketch_per_run)[0]
                         for k, t, _ in runs])
    pt = np.concatenate([run_sketch(k, t, sketch_per_run)[1]
                         for k, t, _ in runs])
    order = np.lexsort((pt, pk))
    q = (np.arange(1, m, dtype=np.int64) * len(order)) // m
    s_keys = jnp.asarray(pk[order][np.clip(q, 0, len(order) - 1)]) \
        if len(order) else jnp.zeros(0, jnp.dtype(pk.dtype))
    s_ties = jnp.asarray(pt[order][np.clip(q, 0, len(order) - 1)]) \
        if len(order) else jnp.zeros(0, jnp.uint32)
    m = s_keys.shape[0] + 1

    # cut every run at the splitters: device classify, host boundaries
    bounds = []
    for k, t, _ in runs:
        L = k.shape[0]
        cap = _pow2(L)
        kp = np.full(cap, pad_value(k.dtype), k.dtype)
        tp = np.full(cap, _HI32, np.uint32)
        kp[:L], tp[:L] = k, t
        note("ext:h2d", kp.nbytes + tp.nbytes)
        bucket = _classify_jit(jnp.asarray(kp), jnp.asarray(tp),
                               jnp.int32(L), s_keys, s_ties, nb=m,
                               use_kernel=bool(use_kernel))
        bucket = np.asarray(bucket)[:L]
        note("ext:d2h", bucket.nbytes)
        # run is sorted → bucket is nondecreasing → interval j is
        # [bounds[j], bounds[j+1])
        bounds.append(np.concatenate(
            [np.searchsorted(bucket, np.arange(m)), [L]]))

    # stream the interval chunks through the device sort
    chunk_len = [int(sum(b[j + 1] - b[j] for b in bounds))
                 for j in range(m)]
    cap = _pow2(max(chunk_len + [1]))
    out = []
    for j in range(m):
        if chunk_len[j] == 0:
            continue
        kc = np.concatenate([k[b[j]:b[j + 1]]
                             for (k, _, _), b in zip(runs, bounds)])
        ic = np.concatenate([i[b[j]:b[j + 1]]
                             for (_, _, i), b in zip(runs, bounds)])
        L = kc.shape[0]
        kp = np.full(cap, pad_value(kc.dtype), kc.dtype)
        ip = np.zeros(cap, np.uint32)
        kp[:L], ip[:L] = kc, ic
        note("ext:h2d", kp.nbytes + ip.nbytes)
        ks, ts, is_ = _device_sort(jnp.asarray(kp), jnp.asarray(ip),
                                   jnp.int32(L), cap=cap)
        ks, ts, is_ = (np.asarray(ks)[:L], np.asarray(ts)[:L],
                       np.asarray(is_)[:L])
        note("ext:d2h", ks.nbytes + ts.nbytes + is_.nbytes)
        out.append((ks, ts, is_))
    k, t, i = (np.concatenate([o[n] for o in out]) for n in range(3))
    return k, t, i


# ---------------------------------------------------------------------------
# the distributed passes (sim_map bodies) and the driver
# ---------------------------------------------------------------------------


def _fit_splitters(sk, st, *, axis: str, p: int, impl):
    """Pass B: pool the per-PE sketches, pick p-1 global splitters.

    ``sk``/``st`` are (p, S) HI-padded sketch planes.  One fused tiled
    all_gather per plane inside the body (tag ``ext:splitters``); the
    quantile pick is the shared RAMS machinery, so the external schedule
    inherits its robustness argument.  Returns host (p-1,) planes.
    """
    wide = sk.dtype == np.uint64

    def body(ks, ts):
        with comm.tagged("ext:splitters"):
            gk = comm.all_gather(ks, axis, tiled=True)
            gt = comm.all_gather(ts, axis, tiled=True)
        if not wide:
            c = ((gk.astype(jnp.uint64) << np.uint64(32))
                 | gt.astype(jnp.uint64))
            spl = quantile_splitters(jnp.sort(c), p)
            return ((spl >> np.uint64(32)).astype(jnp.uint32),
                    spl.astype(jnp.uint32))
        perm = jnp.lexsort((gt, gk))
        gk, gt = gk[perm], gt[perm]
        n_valid = jnp.sum(~((gk == _HI64) & (gt == _HI32)))
        q = (jnp.arange(1, p, dtype=jnp.int64) * n_valid) // p
        q = jnp.clip(q, 0, gk.shape[0] - 1)
        return gk[q], gt[q]

    runner = comm.sim_map(body, axis, p, impl=impl)
    out_k, out_t = jax.jit(runner)(jnp.asarray(sk), jnp.asarray(st))
    return np.asarray(out_k[0]), np.asarray(out_t[0])


def _exchange_pass(kr, ir, counts, s_keys, s_ties, *, axis: str, p: int,
                   slot_cap: int, impl, tag: str, use_kernel: bool,
                   overlap: bool = False):
    """Pass C, one run index: classify against the global splitters and
    route the run slices through one slotted all_to_all; each PE sorts
    what it received.  Returns host (p, p*slot_cap) sorted planes,
    (p,) counts, (p,) overflow.

    ``overlap=True`` streams the route (``_alltoall_route(stream=True)``);
    u32 keys then skip the post-exchange :func:`_sort_planes` entirely —
    the streamed merge folds by the u64 (key, tie) composite, so the
    received buffer already *is* the sorted planes.  u64 keys keep the
    re-sort: their tie plane does not travel through the route, and the
    recomputed (key, tie) lexsort is bitwise-identical either way because
    ties are globally unique.
    """
    cap = kr.shape[1]
    sk_c, st_c = jnp.asarray(s_keys), jnp.asarray(s_ties)
    wide = kr.dtype == np.uint64

    def body(k, i, c):
        with comm.tagged(tag):
            pos = jnp.arange(cap, dtype=jnp.int32)
            valid = pos < c
            tie = jnp.where(valid, _mix32(i), _HI32)
            bucket = _classify_planes(k, tie, sk_c, st_c, p,
                                      use_kernel=use_kernel)
            dest = jnp.where(valid, bucket, p)
            if not wide:
                keys = jnp.where(
                    valid,
                    (k.astype(jnp.uint64) << np.uint64(32))
                    | tie.astype(jnp.uint64), _HI64)
            else:
                keys = jnp.where(valid, k, _HI64)
            shard = SortShard(keys=keys, vals={"idx": i},
                              count=c.astype(jnp.int32))
            out, ovf = _alltoall_route(shard, dest, axis, p, slot_cap,
                                       stream=overlap)
        if overlap and not wide:
            ck = out.keys                     # sorted u64 composite
            return ((ck >> np.uint64(32)).astype(jnp.uint32),
                    ck.astype(jnp.uint32), out.vals["idx"], out.count, ovf)
        ko, to, io_ = _sort_planes(
            (out.keys >> np.uint64(32)).astype(jnp.uint32) if not wide
            else out.keys,
            out.vals["idx"], out.count, cap=out.capacity)
        return ko, to, io_, out.count, ovf

    runner = comm.sim_map(body, axis, p, impl=impl)
    k, t, i, c, o = jax.jit(runner)(jnp.asarray(kr), jnp.asarray(ir),
                                    jnp.asarray(counts, jnp.int32))
    return (np.asarray(k), np.asarray(t), np.asarray(i),
            np.asarray(c), np.asarray(o))


def _merge_barrier(counts, *, axis: str, p: int, impl):
    """Pass D's one collective: psum the per-PE received totals before the
    host merges (tag ``ext:merge`` — the fault lane's merge-pass target).
    Returns the global total.
    """
    def body(c):
        with comm.tagged("ext:merge"):
            return comm.psum(c, axis)

    runner = comm.sim_map(body, axis, p, impl=impl)
    out = jax.jit(runner)(jnp.asarray(counts, jnp.int64))
    return int(np.asarray(out)[0])


def _io_recorder(impl, tag: str, pe: Optional[int] = None):
    """ext:h2d / ext:d2h pseudo-event recorder bound to the active trace
    (CountingCollectives / FaultyCollectives expose ``.trace``; plain
    backends record nothing)."""
    cur = impl if impl is not None else comm.current()
    tr = getattr(cur, "trace", None)
    if tr is None:
        return None
    return lambda direction, nbytes: tr.add(direction, int(nbytes), 1,
                                            tag=tag, pe=pe)


def _psort_external_once(u, n: int, *, axis: str, p: int,
                         policy: ExternalPolicy, impl=None,
                         overlap: bool = False):
    """Run the four external passes once at the current topology.

    ``u`` is the full uint key array (host or device); returns host
    ``(keys (1, p, out_cap), idx (1, p, out_cap), counts (1, p),
    overflow (1, p))`` — the layout psort assembles its answer from, so the
    fault driver's exclude-and-rescale loop composes unchanged.  Raises
    :class:`comm.PEFailure` at trace time under a matching fault plan.

    ``overlap=True`` pipelines both ends of pass C: each slotted exchange
    streams through ``comm.alltoall_stream``, and every received slice is
    folded into a per-PE running merge (``merge_runs``, the kway-kernel
    classifier engine when eligible) as soon as its pass lands, so pass D
    finds the merge already done.  Bitwise-identical: ties are bijective
    in the global index, so any merge order yields the same planes.
    """
    u = np.asarray(u)
    per = -(-max(n, 1) // p)
    B = int(policy.budget)
    R = max(1, -(-per // B))
    s = int(policy.sketch_per_run)
    from .types import local_kernels
    use_kernel = local_kernels().partition
    counts = np.minimum(np.maximum(n - per * np.arange(p), 0),
                        per).astype(np.int64)

    # --- pass A: run formation (host → device → host, per PE) -------------
    io_runs = _io_recorder(impl, "ext:runs")
    runs = []
    for pe in range(p):
        lo = pe * per
        ke = u[lo:lo + counts[pe]]
        ie = (lo + np.arange(counts[pe])).astype(np.uint32)
        runs.append(form_runs(ke, ie, budget=B,
                              double_buffer=policy.double_buffer,
                              io=io_runs))

    # --- pass B: splitter fit on the run sketches -------------------------
    S = R * s
    hi_k = pad_value(u.dtype)
    sk = np.full((p, S), hi_k, u.dtype)
    st = np.full((p, S), _HI32, np.uint32)
    gs = np.ones((p, R), np.int64)
    sklen = np.zeros((p, R), np.int64)
    for pe in range(p):
        for r, (k, t, _) in enumerate(runs[pe]):
            qk, qt, g = run_sketch(k, t, s)
            sk[pe, r * s:r * s + len(qk)] = qk
            st[pe, r * s:r * s + len(qk)] = qt
            gs[pe, r], sklen[pe, r] = g, len(qk)
    s_keys, s_ties = _fit_splitters(sk, st, axis=axis, p=p, impl=impl)

    # --- pass C: per-run slotted exchanges --------------------------------
    received = [[] for _ in range(p)]
    acc: List[Optional[Tuple]] = [None] * p   # overlap: running merge per PE
    recv_counts = np.zeros(p, np.int64)
    overflow = np.zeros(p, np.int64)
    io_merge = _io_recorder(impl, "ext:merge")
    for r in range(R):
        # provision the slot from the sketches (the capacity invariant)
        cap_rd = max(
            int(provision(sk[pe, r * s:r * s + sklen[pe, r]],
                          st[pe, r * s:r * s + sklen[pe, r]],
                          int(gs[pe, r]), s_keys, s_ties, p).max())
            for pe in range(p))
        slot_cap = max(4, int(math.ceil(policy.slot_factor * cap_rd)))
        kr = np.full((p, B), hi_k, u.dtype)
        ir = np.zeros((p, B), np.uint32)
        cr = np.zeros(p, np.int32)
        for pe in range(p):
            if r < len(runs[pe]):
                k, _, i = runs[pe][r]
                kr[pe, :len(k)], ir[pe, :len(k)], cr[pe] = k, i, len(k)
        ko, to, io_, co, oo = _exchange_pass(
            kr, ir, cr, s_keys, s_ties, axis=axis, p=p, slot_cap=slot_cap,
            impl=impl, tag=f"ext:pass{r}", use_kernel=use_kernel,
            overlap=overlap)
        overflow += np.asarray(oo, np.int64)
        for pe in range(p):
            c = int(co[pe])
            recv_counts[pe] += c
            sl = (ko[pe, :c], to[pe, :c], io_[pe, :c])
            if overlap:
                # fold the slice into the running merge while pass r+1's
                # exchange is still ahead — pass D's merge is then a no-op
                acc[pe] = sl if acc[pe] is None else merge_runs(
                    [acc[pe], sl], budget=B, merge=policy.merge,
                    sketch_per_run=s, use_kernel=use_kernel, io=io_merge)
            else:
                received[pe].append(sl)

    # --- pass D: merge barrier + per-PE k-way merge -----------------------
    _merge_barrier(recv_counts, axis=axis, p=p, impl=impl)
    if overlap:
        empty = (np.zeros(0, u.dtype), np.zeros(0, np.uint32),
                 np.zeros(0, np.uint32))
        merged = [acc[pe] if acc[pe] is not None else empty
                  for pe in range(p)]
    else:
        merged = [merge_runs(received[pe], budget=B, merge=policy.merge,
                             sketch_per_run=s, use_kernel=use_kernel,
                             io=io_merge)
                  for pe in range(p)]

    out_counts = np.array([len(m[0]) for m in merged], np.int32)
    out_cap = max(4, int(out_counts.max(initial=1)))
    k_out = np.full((1, p, out_cap), hi_k, u.dtype)
    i_out = np.zeros((1, p, out_cap), np.uint32)
    for pe in range(p):
        c = out_counts[pe]
        k_out[0, pe, :c] = merged[pe][0]
        i_out[0, pe, :c] = merged[pe][2]
    return (k_out, i_out, out_counts.reshape(1, p),
            overflow.astype(np.int32).reshape(1, p))
