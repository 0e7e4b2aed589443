"""Measurement-driven calibration of the α/β cost model (ROADMAP items 1–2).

Two measurement phases on the **sim backend** (single process, chunked
vmap over emulated PEs — p = 64…1024):

1. **Primitive microbenchmarks** → the machine profile.  The way machine
   constants are derived in "Practical Massively Parallel Sorting"
   (arXiv 1410.6754): each parameter is isolated by a collective that
   depends on (almost) nothing else —

     * α      — per-launch cost of a chained tiny-payload ``ppermute``;
     * β      — payload slope of the same ``ppermute`` (s per word/PE);
     * α_c,
       α_hop — tiny-payload ``all_gather`` launch cost regressed on the
               torus pipeline depth p^(1/3) across the swept p;
     * local_rate — ``jnp.sort`` throughput in model words (m·lg m / t).

   The result is a measured :class:`repro.core.selection.CostModel`
   written to ``profiles/<machine>.json`` (load with ``CostModel.load``,
   pass to ``select_algorithm`` / ``psort(algorithm="auto",
   cost_model=...)``).

2. **Algorithm sweep** → crossover validation + the CI perf artifact.
   The four regime algorithms (GatherM / RFIS / RQuick / RAMS) run over
   n/p × p, collecting per cell the counted collective trace
   (``repro.core.api.trace_collectives`` — the measured Table I) and
   wall-clock.  The script reports predicted-vs-measured regime winners
   per (n/p, p) (the Fig. 1 analogue) and dumps every cell into
   ``BENCH_calibrate.json``.  A whole-program NNLS fit of
   ``t ≈ α·p2p + α_c·fused + α_hop·hops + β·words + local/rate`` over the
   sweep cells is stashed in the profile's ``meta`` as a diagnostic — on
   a CPU sim host it degenerates (wall-clock is dominated by vectorized
   data movement, so the launch terms are unidentifiable), which is
   exactly why the profile itself comes from the microbenchmarks.

A third, optional phase (``--nested P_OUTER P_INNER``) runs the
**two-tier** measurement on a nested (inter × intra) sim mesh: per-axis
primitive microbenchmarks fit distinct inner/outer α and β into the
profile (the ``*_inner`` fields of :class:`CostModel`, charged to the
intra-axis levels of hierarchical RAMS by ``cost_rams(mesh_shape=...)``),
and a nested-vs-flat RAMS sweep adds ``rams@PoxPi`` wall-clock cells next
to the flat oracle so ``tools/check_bench.py`` gates the hierarchical
path too.

Typical runs::

    PYTHONPATH=src python benchmarks/calibrate.py --p 64 256 1024
    PYTHONPATH=src python benchmarks/calibrate.py --p 64 --fast
    PYTHONPATH=src python benchmarks/calibrate.py --p 64 256 --nested 8 8
    PYTHONPATH=src python benchmarks/calibrate.py --experiments-only

The p = 1024 column compiles ~20 programs of 1024 emulated PEs; expect
10–20 minutes for the full three-p run on a laptop-class CPU.
"""
import argparse
import json
import math
import os
import platform
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

from common import emit, timeit                                    # noqa: E402

import jax                                                         # noqa: E402
import jax.numpy as jnp                                            # noqa: E402

from repro.core import comm, selection                             # noqa: E402
from repro.core.api import SortConfig, psort, trace_collectives    # noqa: E402
from repro.core.selection import CostModel                         # noqa: E402
from repro.data.distributions import generate_instance             # noqa: E402

ALGOS = ("gatherm", "rfis", "rquick", "rams")

# n/p exponents (log2) per emulated PE count.  The 1024 column is thinned:
# each cell is a fresh XLA compile of a 1024-PE program.
EXPS = {
    64: [-8, -5, -3, -1, 0, 1, 2, 4, 6],
    256: [-8, -5, -3, -1, 0, 1, 2, 4, 6],
    1024: [-8, -3, -1, 0, 2, 4],
}
EXPS_FAST = [-3, 0, 2]


def eligible(algo: str, e: int, p: int) -> bool:
    """Measurement windows: each algorithm is swept over its regime plus a
    margin for locating the crossover, not over grid cells where it is
    pathological (GatherM's concentrated output at dense n, RFIS's
    O((n/√p)²) tie ranking)."""
    if algo == "gatherm":
        return e <= 0
    if algo == "rfis":
        return e <= (4 if p >= 1024 else 6)
    if algo == "rams":
        return e >= 0
    return True


def cell_features(n: int, p: int, algo: str, mesh_shape=None,
                  **algo_kw) -> dict:
    """Counted-trace feature vector of the cell *as timed* — extra
    ``algo_kw`` (e.g. an explicit ``level_bits``) must match the psort
    call so the NNLS fit regresses wall-clock against the schedule that
    actually ran."""
    if mesh_shape is not None:
        cfg = SortConfig(mesh_shape=mesh_shape, algorithm=algo,
                         algo_kw=algo_kw)
    else:
        cfg = SortConfig(p=p, algorithm=algo, algo_kw=algo_kw)
    tr = trace_collectives(n, cfg)
    npp = n / p
    return {
        "p2p": tr.p2p_launches,
        "fused": tr.fused_launches,
        "hops": tr.fused_hops(p),
        "wire_words": tr.wire_bytes() / selection.BYTES_PER_WORD,
        "local_words": npp * math.log2(max(2, n)) + npp,
        "counts": tr.counts(),
        "wire_bytes": tr.wire_bytes(),
    }


_FEATURES = ("p2p", "fused", "hops", "wire_words", "local_words")


# ---------------------------------------------------------------------------
# Phase 1: primitive microbenchmarks → the machine profile
# ---------------------------------------------------------------------------


def _median_seconds(jitted, *args, iters=5):
    jax.block_until_ready(jitted(*args))          # compile + warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_ppermute(p: int, w: int, chain: int = 16) -> float:
    """Seconds per ppermute launch of a w-word/PE payload at axis size p."""
    perm = [(i, (i + 1) % p) for i in range(p)]

    def body(v):
        for _ in range(chain):
            v = comm.ppermute(v, "pe", perm) + 1  # +1 defeats CSE
        return v

    f = jax.jit(comm.sim_map(body, "pe", p))
    x = jnp.zeros((p, w), jnp.int32)
    return _median_seconds(f, x) / chain


def bench_all_gather(p: int, w: int, chain: int = 8) -> float:
    """Seconds per fused-collective launch (tiny all_gather) at size p."""

    def body(v):
        acc = v
        for _ in range(chain):
            g = comm.all_gather(acc, "pe", tiled=True)    # (p*w,)
            acc = g.reshape(p, w)[0] + 1                  # (w,), chained
        return acc

    f = jax.jit(comm.sim_map(body, "pe", p))
    x = jnp.zeros((p, w), jnp.int32)
    return _median_seconds(f, x) / chain


def _local_sort_seconds(p: int, m: int, kernel: bool = False) -> float:
    r = np.random.default_rng(0)
    if kernel:
        from repro.kernels.bitonic import local_sort_fast
        f = jax.jit(lambda v: local_sort_fast(v))
        x = jnp.asarray(r.integers(0, 2**32, size=m, dtype=np.int64)
                        .astype(np.uint32))
        return _median_seconds(f, x)
    f = jax.jit(comm.sim_map(lambda v: jnp.sort(v), "pe", p))
    x = jnp.asarray(r.integers(0, 2**31, size=(p, m), dtype=np.int64)
                    .astype(np.int32))
    return _median_seconds(f, x)


def bench_local_sort_rate(p: int, m: int = 1 << 14,
                          kernel: bool = False) -> float:
    """Local words/s in model units: per-PE sort of m words costs
    m·lg(m)/local_rate on the host that co-executes all p PEs.

    ``kernel=True`` times the Pallas bitonic path on one shard instead
    (interpret mode off-TPU — a machinery check, not silicon perf)."""
    return m * math.log2(m) / _local_sort_seconds(p, m, kernel)


def _partition_seconds(p: int, m: int, nb: int, kernel: bool = False) -> float:
    from repro.kernels.partition import partition_buckets
    r = np.random.default_rng(0)
    keys = np.sort(r.integers(0, 2**32, size=(p, m), dtype=np.int64)
                   .astype(np.uint32), axis=1)
    ties = r.integers(0, 2**32, size=(p, m), dtype=np.int64).astype(np.uint32)
    sk = jnp.asarray(np.sort(r.integers(0, 2**32, size=nb - 1, dtype=np.int64)
                             .astype(np.uint32)))
    st = jnp.asarray(np.zeros(nb - 1, np.uint32))

    def body(k, t):
        return partition_buckets(k, t, sk, st, n_buckets=nb,
                                 use_kernel=kernel)

    if kernel:
        f = jax.jit(body)
        return _median_seconds(f, jnp.asarray(keys[0]), jnp.asarray(ties[0]))
    f = jax.jit(comm.sim_map(body, "pe", p))
    return _median_seconds(f, jnp.asarray(keys), jnp.asarray(ties))


def bench_partition_rate(p: int, m: int = 1 << 14, nb: int = 64,
                         kernel: bool = False) -> float:
    """Partition words/s in model units: classify + rank + histogram of m
    locally-sorted words into nb buckets costs m·lg(nb)/partition_rate
    (the searchsorted depth — the fused kernel's branchless scan is
    O(m·nb) arithmetic but one memory pass, which is what the wall-clock
    actually tracks).  ``kernel=False`` times the jnp reference the sim
    backend runs, co-executing all p PEs like the other primitives;
    ``kernel=True`` times the fused Pallas kernel on one shard."""
    return m * math.log2(max(2, nb)) / _partition_seconds(p, m, nb, kernel)


# ---------------------------------------------------------------------------
# Two-tier (nested-axis) microbenchmarks: distinct inner/outer α, β
# ---------------------------------------------------------------------------


def bench_axis_ppermute(p_o: int, p_i: int, axis: str, w: int,
                        chain: int = 16) -> float:
    """Seconds per ppermute launch on ONE real axis of a nested
    (inter, intra) sim mesh — the per-axis analogue of
    :func:`bench_ppermute` (calls naming a real axis pass through the
    nested view unchanged)."""
    axes = (("inter", p_o), ("intra", p_i))
    size = p_o if axis == "inter" else p_i
    perm = [(i, (i + 1) % size) for i in range(size)]

    def body(v):
        for _ in range(chain):
            v = comm.ppermute(v, axis, perm) + 1  # +1 defeats CSE
        return v

    f = jax.jit(comm.sim_map(body, "sort", nested=axes))
    x = jnp.zeros((p_o, p_i, w), jnp.int32)
    return _median_seconds(f, x) / chain


def bench_axis_all_gather(p_o: int, p_i: int, axis: str, w: int,
                          chain: int = 8) -> float:
    """Seconds per fused-collective launch (tiny all_gather) on one real
    axis of a nested mesh."""
    axes = (("inter", p_o), ("intra", p_i))
    size = p_o if axis == "inter" else p_i

    def body(v):
        acc = v
        for _ in range(chain):
            g = comm.all_gather(acc, axis, tiled=True)    # (size*w,)
            acc = g.reshape(size, w)[0] + 1               # (w,), chained
        return acc

    f = jax.jit(comm.sim_map(body, "sort", nested=axes))
    x = jnp.zeros((p_o, p_i, w), jnp.int32)
    return _median_seconds(f, x) / chain


def measure_nested_profile(model: CostModel, p_o: int, p_i: int) -> CostModel:
    """Fit the *inner-axis* machine constants from per-axis primitives on
    a (p_o × p_i) nested sim mesh and attach them to ``model``.

    On the single-host sim backend both axes run at memory speed, so the
    inner/outer split mostly demonstrates the machinery; on a real
    inter-host × intra-host slice the same sweep separates NIC-bound from
    ICI-bound constants (the two-tier measurement of arXiv 1410.6754)."""
    import dataclasses as _dc
    w_lo, w_hi = 64, 4096
    prior = selection.DEFAULT_MODEL
    a_i = bench_axis_ppermute(p_o, p_i, "intra", 1)
    t_lo = bench_axis_ppermute(p_o, p_i, "intra", w_lo)
    t_hi = bench_axis_ppermute(p_o, p_i, "intra", w_hi)
    b_i = max((t_hi - t_lo) / (w_hi - w_lo), 1e-3 * prior.beta)
    ac_i = max(bench_axis_all_gather(p_o, p_i, "intra", 1),
               1e-3 * prior.alpha_c)
    a_o = bench_axis_ppermute(p_o, p_i, "inter", 1)
    ac_o = bench_axis_all_gather(p_o, p_i, "inter", 1)
    meta = dict(model.meta)
    meta["nested_microbench"] = {
        "mesh_shape": [p_o, p_i],
        "intra": {"alpha": a_i, "alpha_c": ac_i, "beta": b_i},
        "inter": {"alpha": a_o, "alpha_c": ac_o},
        "method": "per-axis primitives on the nested sim mesh "
                  "(two-tier 1410.6754-style)",
    }
    return _dc.replace(model, alpha_inner=float(a_i),
                       alpha_c_inner=float(ac_i), beta_inner=float(b_i),
                       meta=meta)


def run_nested_sweep(p_o: int, p_i: int, iters: int, exps=(0, 2, 4)):
    """Nested-vs-flat RAMS wall-clock cells at the same total p.

    Cells land in the bench JSON under algorithm ``rams@{p_o}x{p_i}``
    (nested) next to ``rams-flat@{p_o}x{p_i}`` (the flat-axis oracle run
    with the *same* aligned level schedule), so ``tools/check_bench.py``
    gates the hierarchical path's trajectory too.  Both labels carry the
    mesh shape: the plain ``rams`` cells of :func:`run_sweep` time the
    default schedule and must not be overwritten, and the ``@`` marker
    keeps all of these out of the crossover winner tables."""
    from repro.core.rams import nested_level_bits
    p = p_o * p_i
    bits = tuple(nested_level_bits(p_o, p_i))
    cells = []
    for e in exps:
        n = max(1, int(p * 2.0 ** e))
        x = generate_instance("Uniform", p, n, seed=11).astype(np.int32)
        for label, cfg, feat_kw in (
                (f"rams@{p_o}x{p_i}",
                 SortConfig(mesh_shape=(p_o, p_i), algorithm="rams",
                            backend="sim"),
                 {"mesh_shape": (p_o, p_i)}),
                (f"rams-flat@{p_o}x{p_i}",
                 SortConfig(p=p, algorithm="rams", backend="sim",
                            algo_kw={"level_bits": bits}),
                 {"level_bits": bits})):
            us = timeit(lambda: np.asarray(psort(x, config=cfg)),
                        warmup=1, iters=iters)
            feat = cell_features(n, p, "rams", **feat_kw)
            cell = {"p": p, "e": e, "n": n, "algorithm": label,
                    "us": us, "seconds": us * 1e-6, **feat}
            cells.append(cell)
            emit(f"calibrate/nested{p_o}x{p_i}/npp2^{e}/{label}", us,
                 f"p2p={feat['p2p']} fused={feat['fused']} "
                 f"wire={feat['wire_bytes']}B")
    return cells


def measure_profile(ps, name: str) -> CostModel:
    """Microbenchmark the five machine constants on the sim backend.

    All payload-bearing measurements run at the largest swept p: the sim
    host co-executes every emulated PE, so per-PE costs are p-dependent —
    the profile models the machine actually used for the sweep."""
    pmax = max(ps)
    w_lo, w_hi = 64, 4096
    alpha = bench_ppermute(pmax, 1)
    t_lo, t_hi = bench_ppermute(pmax, w_lo), bench_ppermute(pmax, w_hi)
    beta = max((t_hi - t_lo) / (w_hi - w_lo), 1e-3 * selection.DEFAULT_MODEL.beta)

    hops = np.array([float(p) ** (1.0 / 3.0) for p in ps])
    t_coll = np.array([bench_all_gather(p, 1) for p in ps])
    prior = selection.DEFAULT_MODEL
    if len(ps) >= 2:
        slope, intercept = np.polyfit(hops, t_coll, 1)
        alpha_hop = max(float(slope), 1e-3 * prior.alpha_hop)
        alpha_c = max(float(intercept), 1e-3 * prior.alpha_c)
    else:
        alpha_hop = prior.alpha_hop
        alpha_c = max(float(t_coll[0]) - alpha_hop * float(hops[0]),
                      1e-3 * prior.alpha_c)
    local_rate = bench_local_sort_rate(pmax)
    partition_rate = bench_partition_rate(pmax)
    io_beta = bench_io_rate()
    overlap_io = measure_overlap()
    overlap_stream = measure_stream_overlap()
    # the model has one overlap knob shared by the external (io) and
    # in-core (wire) discounts; fit it from the larger demonstrated hiding
    # so a backend that overlaps either lane gets credit — on CPU sim both
    # measure ~0 and the β terms stay undiscounted
    overlap = max(overlap_io, overlap_stream)
    # kernel variants run in interpret mode off-TPU: one small shard each,
    # recorded for the bench trajectory (not used as profile constants)
    sort_kernel_rate = bench_local_sort_rate(1, m=1 << 11, kernel=True)
    partition_kernel_rate = bench_partition_rate(1, m=1 << 12, kernel=True)
    return CostModel(
        name=name,
        alpha=float(alpha), alpha_c=float(alpha_c),
        alpha_hop=float(alpha_hop), beta=float(beta),
        local_rate=float(local_rate),
        partition_rate=float(partition_rate),
        slot_overhead=prior.slot_overhead,
        io_beta=float(io_beta), overlap=float(overlap),
        meta={
            "microbench": {
                "method": "primitive microbenchmarks (arXiv 1410.6754 style)",
                "p": list(ps), "p_payload": pmax,
                "ppermute_s": {"w1": alpha, f"w{w_lo}": t_lo, f"w{w_hi}": t_hi},
                "all_gather_s": {str(p): float(t) for p, t in zip(ps, t_coll)},
                "local_sort_words_s": float(local_rate),
                "local_sort_kernel_words_s": float(sort_kernel_rate),
                "partition_words_s": float(partition_rate),
                "partition_kernel_words_s": float(partition_kernel_rate),
                "io_s_word": float(io_beta),
                "overlap_fraction": float(overlap),
                "overlap_io_fraction": float(overlap_io),
                "overlap_stream_fraction": float(overlap_stream),
                "host": platform.node(),
                "backend": "sim",
            },
        })


def fit_profile(cells, name: str) -> CostModel:
    """Non-negative least squares of the 5-parameter machine profile over
    measured (features, seconds) cells.  Parameters the data cannot
    identify (zero weight) fall back to a small fraction of the prior so
    the regime structure stays non-degenerate."""
    A = np.array([[c[f] for f in _FEATURES] for c in cells], float)
    t = np.array([c["seconds"] for c in cells], float)
    try:
        from scipy.optimize import nnls
        theta, _ = nnls(A, t)
    except Exception:                     # scipy-less fallback
        theta, *_ = np.linalg.lstsq(A, t, rcond=None)
        theta = np.clip(theta, 0.0, None)
    pred = A @ theta
    ss_res = float(np.sum((t - pred) ** 2))
    ss_tot = float(np.sum((t - t.mean()) ** 2)) or 1.0
    r2 = 1.0 - ss_res / ss_tot

    prior = selection.DEFAULT_MODEL
    floors = (prior.alpha, prior.alpha_c, prior.alpha_hop, prior.beta,
              1.0 / prior.local_rate)
    alpha, alpha_c, alpha_hop, beta, inv_rate = (
        max(v, 1e-3 * f) for v, f in zip(theta, floors))
    return CostModel(
        name=name,
        alpha=alpha, alpha_c=alpha_c, alpha_hop=alpha_hop, beta=beta,
        local_rate=1.0 / inv_rate,
        slot_overhead=prior.slot_overhead,
        meta={
            "fit": {
                "r2": r2,
                "theta": [float(v) for v in theta],
                "features": list(_FEATURES),
                "n_cells": len(cells),
                "host": platform.node(),
                "backend": "sim",
            },
        })


def _winner_sequence(rows):
    """[(e, winner)] → [(e, prev, new)] transition list."""
    out, prev = [], None
    for e, w in rows:
        if w != prev and prev is not None:
            out.append((e, prev, w))
        prev = w
    return out


def measured_crossovers(cells, p: int):
    by_e = {}
    for c in cells:
        if c["p"] != p or "@" in c["algorithm"]:   # skip nested-mesh cells
            continue
        by_e.setdefault(c["e"], []).append((c["seconds"], c["algorithm"]))
    rows = [(e, min(v)[1]) for e, v in sorted(by_e.items())]
    return rows, _winner_sequence(rows)


def predicted_crossovers(p: int, exps, model: CostModel):
    rows = [(e, selection.select_algorithm(max(1, int(p * 2.0 ** e)), p,
                                           model=model)) for e in sorted(exps)]
    return rows, _winner_sequence(rows)


def run_sweep(ps, exps_override, iters: int):
    cells = []
    for p in ps:
        exps = exps_override or EXPS.get(p, EXPS[256])
        seen = set()
        for e in exps:
            n = max(1, int(p * 2.0 ** e))
            for algo in ALGOS:
                if not eligible(algo, e, p) or (algo, n) in seen:
                    continue
                seen.add((algo, n))
                x = generate_instance("Uniform", p, n, seed=11).astype(np.int32)
                cfg = SortConfig(p=p, algorithm=algo, backend="sim")
                us = timeit(lambda: np.asarray(psort(x, config=cfg)),
                            warmup=1, iters=iters)
                feat = cell_features(n, p, algo)
                cell = {"p": p, "e": e, "n": n, "algorithm": algo,
                        "us": us, "seconds": us * 1e-6, **feat}
                cells.append(cell)
                emit(f"calibrate/p{p}/npp2^{e}/{algo}", us,
                     f"p2p={feat['p2p']} fused={feat['fused']} "
                     f"wire={feat['wire_bytes']}B")
    return cells


def run_local_bench(pmax: int):
    """Local-phase wall-clock cells (sort vs partition, jnp vs Pallas
    kernel) for the CI trajectory gate.  They carry no counted-trace
    features, so they merge into the JSON's ``bench`` mapping only —
    never into the NNLS fit cells.  The ``p`` key labels the sweep's
    pmax for stable cell addressing (the kernel variants time one shard
    in interpret mode); ``e`` is log2 of the per-shard word count."""
    rows = []
    for label, m, kernel in (("local/sort_rate", 1 << 14, False),
                             ("local/sort_kernel", 1 << 11, True),
                             ("local/partition_rate", 1 << 14, False),
                             ("local/partition_kernel", 1 << 12, True)):
        p_run = 1 if kernel else pmax
        if label.startswith("local/sort"):
            t = _local_sort_seconds(p_run, m, kernel=kernel)
        else:
            t = _partition_seconds(p_run, m, 64, kernel=kernel)
        us = t * 1e6
        rows.append({"p": pmax, "e": int(math.log2(m)),
                     "algorithm": label, "us": us})
        emit(f"calibrate/{label}", us, f"m=2^{int(math.log2(m))}")
    return rows


def bench_io_rate(m: int = 1 << 18, iters: int = 5) -> float:
    """Host↔device streaming seconds per 32-bit word (``CostModel.io_beta``):
    a device_put + device_get round-trip of an m-word buffer, halved.  On
    the CPU sim backend this is a memcpy pair — the measurement matters on
    accelerators, where it is the external lane's PCIe term."""
    x = np.zeros(m, np.int32)
    ts = []
    jax.block_until_ready(jax.device_put(x))          # warm the path
    for _ in range(iters):
        t0 = time.perf_counter()
        np.asarray(jax.block_until_ready(jax.device_put(x)))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) / (2 * m)


def _form_runs_seconds(m: int, budget: int, double_buffer: bool) -> float:
    from repro.core import external as ext
    r = np.random.default_rng(0)
    keys = r.integers(0, 2**32, size=m, dtype=np.int64).astype(np.uint32)
    idx = np.arange(m, dtype=np.uint32)
    ext.form_runs(keys, idx, budget=budget,
                  double_buffer=double_buffer)        # compile + warm
    t0 = time.perf_counter()
    ext.form_runs(keys, idx, budget=budget, double_buffer=double_buffer)
    return time.perf_counter() - t0


def measure_overlap(m: int = 1 << 16, budget: int = 1 << 13) -> float:
    """``CostModel.overlap``: the fraction of run-formation wall-clock the
    double-buffered copies hide, measured as 1 - t(db)/t(serial), clamped
    to [0, 1).  ~0 on the synchronous CPU sim backend; meaningful where
    device_put is truly async."""
    t_serial = _form_runs_seconds(m, budget, double_buffer=False)
    t_db = _form_runs_seconds(m, budget, double_buffer=True)
    return float(min(0.99, max(0.0, 1.0 - t_db / max(t_serial, 1e-12))))


def _stream_exchange_seconds(p: int, w: int) -> float:
    """One chunk-granular slotted exchange (``comm.alltoall_stream`` with a
    staging fold) of p·w words/PE on the sim backend."""
    def body(v):
        def fold(acc, chunk, src):
            return jax.lax.dynamic_update_slice(
                acc, chunk.reshape(1, w), (src.astype(jnp.int32),
                                           jnp.int32(0)))
        init = jnp.zeros((p, w), jnp.int32)
        return comm.alltoall_stream(v, "pe", fold, init, p)

    f = jax.jit(comm.sim_map(body, "pe", p))
    x = jnp.zeros((p, p * w), jnp.int32)
    return _median_seconds(f, x)


def _overlap_pair_us(p: int = 8, e: int = 8, algo: str = "rams",
                     iters: int = 2):
    """(barrier µs, streamed µs) of the same in-core psort cell — the
    pipelined exchange+merge (``overlap=True``) against the barrier path it
    is bitwise-equal to."""
    n = p << e
    x = generate_instance("Uniform", p, n, seed=11).astype(np.int32)
    cfg = SortConfig(p=p, algorithm=algo, backend="sim")
    us_b = timeit(lambda: np.asarray(psort(x, config=cfg)),
                  warmup=1, iters=iters)
    us_s = timeit(lambda: np.asarray(
        psort(x, config=cfg.replace(overlap=True))), warmup=1, iters=iters)
    return us_b, us_s


def measure_stream_overlap(p: int = 8, e: int = 8) -> float:
    """In-core counterpart of :func:`measure_overlap`: the fraction of the
    in-core exchange+merge the chunk-granular pipeline hides, measured
    end-to-end as 1 - t(streamed)/t(barrier), clamped to [0, 1).

    On the synchronous CPU sim backend nothing actually overlaps — the
    per-chunk local sorts and the k-way merge tree are exposed work on top
    of the same wire traffic — so the streamed path measures *slower* and
    this clamps to 0, keeping ``CostModel.overlap`` honest: the model only
    discounts the β terms where the machine demonstrably hides them."""
    us_b, us_s = _overlap_pair_us(p=p, e=e)
    return float(min(0.99, max(0.0, 1.0 - us_s / max(us_b, 1e-9))))


def run_overlap_bench(pmax: int):
    """Exchange/merge-overlap wall-clock cells for the CI trajectory gate,
    in the ``run_local_bench`` shape (no counted-trace features):

      * ``overlap/stream_rate``  — one chunk-granular slotted exchange
        (p = 8, 2^10 words per destination) with a staging fold;
      * ``overlap/e2e``          — streamed in-core ``psort(overlap=True)``
        at p = 8, n/p = 2^8 (rams);
      * ``overlap/e2e_barrier``  — the barrier path of the identical cell,
        so the gate tracks both trajectories and the exposed-pipeline
        ratio on CPU sim stays visible in the artifact.
    """
    rows = []
    p, w = 8, 1 << 10
    us = _stream_exchange_seconds(p, w) * 1e6
    rows.append({"p": pmax, "e": int(math.log2(w)),
                 "algorithm": "overlap/stream_rate", "us": us})
    emit("calibrate/overlap/stream_rate", us, f"p={p} w=2^{int(math.log2(w))}")

    e = 8
    us_b, us_s = _overlap_pair_us(p=p, e=e)
    rows.append({"p": pmax, "e": e, "algorithm": "overlap/e2e", "us": us_s})
    rows.append({"p": pmax, "e": e, "algorithm": "overlap/e2e_barrier",
                 "us": us_b})
    ratio = us_s / max(us_b, 1e-9)
    emit("calibrate/overlap/e2e", us_s,
         f"p={p} n/p=2^{e} rams streamed (barrier {us_b:.0f}us, "
         f"ratio {ratio:.2f})")
    return rows


def run_external_bench(pmax: int):
    """External-lane wall-clock cells for the CI trajectory gate, in the
    ``run_local_bench`` shape (no counted-trace features — they join the
    JSON's ``bench`` mapping only):

      * ``external/run_formation`` — pass A, 2^14 words through a 2^11
        budget (8 double-buffered device round-trips);
      * ``external/kway_merge`` — pass D, classifier engine over the 8
        formed runs;
      * ``external/e2e`` — the full four-pass ``psort(external=...)`` at
        p = 8, n/p = 2^8, budget 2^6 (4 runs/PE).
    """
    from repro.core import external as ext
    from repro.core.external import ExternalPolicy
    rows = []
    m, budget = 1 << 14, 1 << 11
    r = np.random.default_rng(0)
    keys = r.integers(0, 2**32, size=m, dtype=np.int64).astype(np.uint32)
    idx = np.arange(m, dtype=np.uint32)

    us = timeit(lambda: ext.form_runs(keys, idx, budget=budget),
                warmup=1, iters=2)
    rows.append({"p": pmax, "e": int(math.log2(m)),
                 "algorithm": "external/run_formation", "us": us})
    emit("calibrate/external/run_formation", us,
         f"m=2^{int(math.log2(m))} budget=2^{int(math.log2(budget))}")

    runs = ext.form_runs(keys, idx, budget=budget)
    us = timeit(lambda: ext.merge_runs(runs, budget=budget),
                warmup=1, iters=2)
    rows.append({"p": pmax, "e": int(math.log2(m)),
                 "algorithm": "external/kway_merge", "us": us})
    emit("calibrate/external/kway_merge", us, f"runs={len(runs)}")

    p, e = 8, 8
    n = p << e
    x = generate_instance("Uniform", p, n, seed=11).astype(np.int32)
    cfg = SortConfig(p=p, backend="sim", external=ExternalPolicy(budget=1 << 6))
    us = timeit(lambda: np.asarray(psort(x, config=cfg)), warmup=1, iters=2)
    rows.append({"p": pmax, "e": e, "algorithm": "external/e2e", "us": us})
    emit("calibrate/external/e2e", us,
         f"p={p} n/p=2^{e} budget=2^6 runs=4")
    return rows


EXTERNAL_GRID = ((256, 4, 16), (256, 4, 32), (1024, 8, 32), (1024, 8, 64))


def external_rows():
    """The "External memory" grid: per-pass counted traces of the
    out-of-core lane (``trace_collectives(external=...)`` — seeded input,
    trace-time counts, no wall-clock, so ``tools/check_docs.py`` can diff
    the regenerated file).  The point of the grid: wire volume is paid
    once per run pass (R slotted all_to_alls) while the host↔device
    stream (io bytes) covers every element twice — run formation and
    merge — independent of R."""
    from repro.core.external import ExternalPolicy
    rows = []
    for n, p, budget in EXTERNAL_GRID:
        tr = trace_collectives(n, SortConfig(
            p=p, external=ExternalPolicy(budget=budget)))
        per = -(-n // p)
        runs = -(-per // budget)
        passes = sum(1 for t in tr.tags() if t.startswith("ext:pass"))
        a2a = tr.filter(primitive="all_to_all")
        rows.append((n, p, budget, runs, passes, a2a.counts()["all_to_all"],
                     tr.wire_bytes(), tr.io_bytes(),
                     tr.filter(tag="ext:runs").io_bytes(),
                     tr.filter(tag="ext:merge").io_bytes()))
    return rows


SUBGROUP_PS = (4, 16, 64)
SUBGROUP_DS = (1, 2, 4)

NESTED_GRID = ((2, 8), (4, 16), (16, 64))


def nested_rows(npp: int = 16):
    """The "Hierarchical mesh" grid: per-PE counted traces of nested RAMS
    over (p_outer × p_inner) sim meshes, split by real axis.

    Deterministic (trace-time counts, no wall-clock), so
    ``tools/check_docs.py`` can diff the regenerated file.  The point of
    the grid: the slow *inter* axis carries the shuffle plus exactly one
    level's all_to_all — every later level is intra-only, so inter-axis
    volume stays flat as levels deepen."""
    rows = []
    for p_o, p_i in NESTED_GRID:
        p = p_o * p_i
        n = npp * p
        tr = trace_collectives(n, SortConfig(mesh_shape=(p_o, p_i),
                                             algorithm="rams"))
        ax = tr.by_axis()
        inter_a2a = tr.filter(primitive="all_to_all", axis="inter")
        rows.append((p_o, p_i, n, len(tr.tags()) - 1,
                     ax["inter"]["launches"], ax["inter"]["wire_bytes"],
                     ax["intra"]["launches"], ax["intra"]["wire_bytes"],
                     " ".join(inter_a2a.tags())))
    return rows


def subgroup_rows(model: CostModel, npp: int = 32):
    """The "Subgroup sort" grid: per-PE counted collective traces of the
    auto-selected algorithm (under ``model``) over (d, p_sort) sim meshes.

    Deterministic (``trace_collectives`` counts at trace time, no
    wall-clock), so ``tools/check_docs.py`` can diff the regenerated file.
    The point of the grid: the per-PE trace is **independent of d** —
    every collective resolves relative to the sort axis, so adding data
    rows multiplies tenants, not per-PE communication.
    """
    rows = []
    for p in SUBGROUP_PS:
        n = npp * p
        algo = selection.select_algorithm(n, p, model=model)
        for d in SUBGROUP_DS:
            tr = trace_collectives(n, SortConfig(p=p, algorithm=algo), d=d)
            rows.append((p, d, n, algo, tr.p2p_launches, tr.fused_launches,
                         tr.wire_bytes()))
    return rows


QUERY_GRID_P = (8, 64, 256)
QUERY_GRID = (("rank_of_key", 32, None), ("range_query", 32, None),
              ("percentile", 32, None), ("percentile", 64, None),
              ("top_k", 32, 16), ("sort", 32, None))


def query_rows(npp: int = 1 << 14, batch: int = 8):
    """The "Query serving" grid: per-PE counted traces of the selection
    fast paths (``core/queries.py``) next to the full sort that would
    otherwise answer the same micro-batch.

    Deterministic (trace-time counts, no wall-clock), so
    ``tools/check_docs.py`` can diff the regenerated file.  The point of
    the grid: a selection query's launch count is fixed by the key width
    (``ceil(bits/4)`` refinement rounds) and its wire volume by the batch
    — both independent of n — while the sort's volume is Ω(n/p)."""
    from repro.core.queries import trace_query
    rows = []
    for p in QUERY_GRID_P:
        n = npp * p
        for kind, bits, k in QUERY_GRID:
            dtype = np.uint32 if bits == 32 else np.uint64
            tr = trace_query(kind, n, p, batch=batch, dtype=dtype, k=k)
            rows.append((p, n, kind, bits, tr.p2p_launches,
                         tr.fused_launches, tr.wire_bytes()))
    return rows


def write_experiments(path: str, model: CostModel):
    """Regenerate EXPERIMENTS.md: the regime tables ``selection.py``'s
    docstring points at, the subgroup-sort grid, and the profile-JSON
    schema, under the given machine profile."""
    lines = [
        "# EXPERIMENTS",
        "",
        "Regime tables of `repro.core.selection.select_algorithm` — which",
        "algorithm the α/β cost model picks per (n/p, p).  Regenerate after",
        "recalibration with:",
        "",
        "```sh",
        "PYTHONPATH=src python benchmarks/calibrate.py --experiments-only \\",
        "    [--profile profiles/<machine>.json]",
        "```",
        "",
        "(CI's docs job diffs this file against the regenerated output —",
        "edit by rerunning the command, not by hand.)",
        "",
        f"Machine profile: **{model.name}** "
        f"(α={model.alpha:.3g}s, α_c={model.alpha_c:.3g}s, "
        f"α_hop={model.alpha_hop:.3g}s, β={model.beta:.3g}s/word, "
        f"local={model.local_rate:.3g}w/s, "
        f"partition={model.part_rate:.3g}w/s)",
        "",
    ]
    for p in (64, 1024, 262144):
        lines += [f"## p = {p}", "", "| log2(n/p) | n | algorithm |",
                  "|---:|---:|---|"]
        for e, n, algo in selection.regime_table(p, range(-8, 24, 2),
                                                 model=model):
            lines.append(f"| {e} | {n} | {algo} |")
        rows = [(e, a) for e, _, a in
                selection.regime_table(p, range(-8, 24), model=model)]
        seq = " → ".join([rows[0][1]] + [w for _, _, w in
                                         _winner_sequence(rows)])
        lines += ["", f"Regime sequence: {seq}", ""]

    lines += [
        "## Subgroup sort (p_sort × d)",
        "",
        "Batched `psort` over a (d, p_sort) mesh sorts each of the d rows",
        "within its own sort-axis subgroup (`backend=\"sim\"` shown; the",
        "shard_map path shards the same body over a 2-D device mesh).  The",
        "cells are the **per-PE counted collective traces**",
        "(`repro.core.api.trace_collectives(n, p, algo, d=d)`) of the",
        "auto-selected algorithm at n/p = 32: identical down the d column",
        "because every collective resolves relative to the named sort axis",
        "— data-axis rows are isolated tenants, adding rows adds zero",
        "per-PE communication.",
        "",
        "| p_sort | d | n (per row) | algorithm | p2p launches "
        "| fused launches | wire bytes/PE |",
        "|---:|---:|---:|---|---:|---:|---:|",
    ]
    for p, dd, n, algo, p2p, fused, wire in subgroup_rows(model):
        lines.append(f"| {p} | {dd} | {n} | {algo} | {p2p} | {fused} "
                     f"| {wire} |")

    lines += [
        "",
        "## Hierarchical mesh (p_outer × p_inner)",
        "",
        "Nested-axis RAMS (`psort(mesh_shape=(p_outer, p_inner))`) maps the",
        "level schedule onto a hierarchical (inter × intra) mesh: the first",
        "level splits the data across the slow *inter* axis, every later",
        "level recurses inside an *intra* subcube",
        "(`repro.core.comm.NestedCollectives` decomposes the virtual-axis",
        "collectives; `repro.core.rams.nested_level_bits` aligns the",
        "schedule).  Cells are per-PE counted traces",
        "(`trace_collectives(n, mesh_shape=..., algorithm=\"rams\")`, n/p =",
        "16) split by real axis — the inter column carries only the initial",
        "shuffle plus **one** level's all_to_all, independent of depth,",
        "while the run stays bitwise-identical to the flat path.",
        "",
        "| p_outer | p_inner | n | levels | inter launches | inter bytes/PE "
        "| intra launches | intra bytes/PE | inter a2a phases |",
        "|---:|---:|---:|---:|---:|---:|---:|---:|---|",
    ]
    for p_o, p_i, n, lvls, il, ib, al, ab, tags in nested_rows():
        lines.append(f"| {p_o} | {p_i} | {n} | {lvls} | {il} | {ib} "
                     f"| {al} | {ab} | {tags} |")

    lines += [
        "",
        "## External memory (out-of-core)",
        "",
        "`psort(external=ExternalPolicy(budget=...))` streams shards larger",
        "than the device budget through run formation + k-way merge",
        "(docs/ARCHITECTURE.md \"External memory\").  Cells are per-pass",
        "counted traces (`trace_collectives(n, p, external=...)`, seeded",
        "deterministic input): R = ceil(n/p / budget) slotted all_to_all",
        "passes carry the wire volume, while the host↔device stream (the",
        "`ext:h2d`/`ext:d2h` pseudo-events, `CommTrace.io_bytes()`) covers",
        "every element once in each direction per streaming pass —",
        "run formation and merge — independent of R.",
        "",
        "| n | p | budget | runs/PE | a2a passes | a2a launches/PE "
        "| wire bytes/PE | io bytes | io: runs | io: merge |",
        "|---:|---:|---:|---:|---:|---:|---:|---:|---:|---:|",
    ]
    for (n, p, budget, runs, passes, a2a, wire, io_b, io_r,
         io_m) in external_rows():
        lines.append(f"| {n} | {p} | {budget} | {runs} | {passes} | {a2a} "
                     f"| {wire} | {io_b} | {io_r} | {io_m} |")

    lines += [
        "",
        "## Query serving (selection fast paths vs. full sort)",
        "",
        "`launch/sort_serve.py` micro-batches queued queries by kind and",
        "answers each batch with one launch of a `core/queries.py`",
        "primitive over the resident (p, cap) locally-sorted shards — a",
        "batch is a barrier, so every request in it shares the device",
        "latency.  Counting queries (`rank_of_key`, `range_query`) cost one",
        "fused psum; order statistics (`percentile`, `top_k`) run the exact",
        "rank selection — a §III-B butterfly rank window (log2 p p2p steps,",
        "32-bit keys only) then `ceil(bits/4)` counting-verified refinement",
        "rounds of one sketch all_gather + one count psum, plus a verify",
        "psum.  Cells are per-PE counted traces (`trace_query(kind, n, p,",
        "batch=8)`, n/p = 2^14): the selection columns are fixed by the key",
        "width and batch — independent of n — while the full sort's wire",
        "volume is Ω(n/p).  `select_algorithm(n, p, query=...)` encodes the",
        "crossover (`cost_select`): full sort wins only on tiny instances.",
        "",
        "| p | n | query | key bits | p2p launches | fused launches "
        "| wire bytes/PE |",
        "|---:|---:|---|---:|---:|---:|---:|",
    ]
    for p, n, kind, bits, p2p, fused, wire in query_rows():
        lines.append(f"| {p} | {n} | {kind} | {bits} | {p2p} | {fused} "
                     f"| {wire} |")

    lines += [
        "",
        "## `profiles/*.json` schema",
        "",
        "A profile is one serialized `repro.core.selection.CostModel`",
        "(`CostModel.load(path)` / `model.save(path)` round-trip):",
        "",
        "| field | type | meaning |",
        "|---|---|---|",
        "| `name` | str | profile id, conventionally `<os>-<arch>-<backend>` |",
        "| `alpha` | float s | per point-to-point step "
        "(collective-permute launch + link latency) |",
        "| `alpha_c` | float s | per fused-collective launch "
        "(all_gather / psum / all_to_all) |",
        "| `alpha_hop` | float s | per torus hop; fused collectives are "
        "charged `alpha_hop · p^(1/3)` pipeline fill |",
        "| `beta` | float s/word | per 32-bit word on the wire |",
        "| `local_rate` | float words/s | local sort/merge throughput |",
        "| `partition_rate` | float words/s / null | splitter-partition "
        "(classify + rank + histogram) throughput; null in profiles that "
        "predate the fused partition kernel → falls back to `local_rate` |",
        "| `slot_overhead` | float | static slot provisioning factor of "
        "the a2a exchanges |",
        "| `alpha_inner` | float s / null | intra-axis p2p step of a "
        "nested mesh (null = same as `alpha`) |",
        "| `alpha_c_inner` | float s / null | intra-axis fused-collective "
        "launch; intra levels pay no `alpha_hop` fill |",
        "| `beta_inner` | float s/word / null | intra-axis per-word cost "
        "(`--nested` two-tier fit) |",
        "| `io_beta` | float s/word / null | host↔device streaming cost of "
        "the external lane (null = PCIe-class prior via `io_b`) |",
        "| `overlap` | float | fraction of host↔device traffic hidden by "
        "the double-buffered copies (0 = exposed, 1 = hidden) |",
        "| `meta` | object | free-form provenance — `microbench` (the "
        "primitive measurements the constants came from), `sweep_fit` "
        "(whole-program NNLS diagnostic: `r2`, `theta`, `features`, "
        "`n_cells`, host, backend) |",
        "",
        "Profiles are **measured, not hand-edited**: "
        "`benchmarks/calibrate.py` writes them from primitive",
        "microbenchmarks (phase 1) and stashes the sweep regression in "
        "`meta` (phase 2); unknown top-level fields are rejected at load.",
        "",
    ]
    with open(path, "w") as f:
        f.write("\n".join(lines))
    return path


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--p", type=int, nargs="+", default=[64, 256],
                    help="emulated PE counts to sweep (powers of two)")
    ap.add_argument("--exps", type=int, nargs="+", default=None,
                    help="override log2(n/p) grid for every p")
    ap.add_argument("--fast", action="store_true",
                    help=f"thin grid {EXPS_FAST} (smoke runs)")
    ap.add_argument("--iters", type=int, default=2,
                    help="timed iterations per cell (after 1 warmup)")
    ap.add_argument("--nested", type=int, nargs=2, default=None,
                    metavar=("P_OUTER", "P_INNER"),
                    help="two-tier pass on a nested (inter × intra) sim "
                         "mesh: per-axis microbench fits distinct "
                         "inner/outer α, β into the profile, and a "
                         "nested-vs-flat RAMS sweep adds rams@PoxPi cells")
    ap.add_argument("--machine", default=None,
                    help="profile name (default <os>-<arch>-sim)")
    ap.add_argument("--profile-dir", default="profiles")
    ap.add_argument("--profile", default=None,
                    help="existing profile JSON (for --experiments-only)")
    ap.add_argument("--bench-json", default="BENCH_calibrate.json")
    ap.add_argument("--experiments", nargs="?", const="EXPERIMENTS.md",
                    default=None, help="also regenerate EXPERIMENTS.md")
    ap.add_argument("--experiments-only", action="store_true",
                    help="skip the sweep; only write EXPERIMENTS.md")
    args = ap.parse_args(argv)

    if args.experiments_only:
        model = CostModel.load(args.profile) if args.profile \
            else selection.DEFAULT_MODEL
        path = write_experiments(args.experiments or "EXPERIMENTS.md", model)
        print(f"# wrote {path} (profile: {model.name})")
        return 0

    machine = args.machine or \
        f"{platform.system().lower()}-{platform.machine()}-sim"
    exps_override = EXPS_FAST if args.fast else args.exps

    print("name,us_per_call,derived")
    model = measure_profile(args.p, machine)
    print(f"# microbenched profile: α={model.alpha:.3g}  "
          f"α_c={model.alpha_c:.3g}  α_hop={model.alpha_hop:.3g}  "
          f"β={model.beta:.3g}  local_rate={model.local_rate:.3g}  "
          f"partition_rate={model.part_rate:.3g}")
    if args.nested:
        p_o, p_i = args.nested
        model = measure_nested_profile(model, p_o, p_i)
        print(f"# two-tier ({p_o}x{p_i}): α_in={model.alpha_inner:.3g}  "
              f"α_c_in={model.alpha_c_inner:.3g}  "
              f"β_in={model.beta_inner:.3g}")

    cells = run_sweep(args.p, exps_override, args.iters)
    if args.nested:
        cells += run_nested_sweep(p_o, p_i, args.iters,
                                  exps=tuple(EXPS_FAST) if args.fast
                                  else (0, 2, 4))
    local_cells = run_local_bench(max(args.p))
    local_cells += run_external_bench(max(args.p))
    local_cells += run_overlap_bench(max(args.p))
    # whole-program regression over the sweep — diagnostic only (see
    # module docstring); kept in meta so the two views can be compared
    sweep_fit = fit_profile(cells, machine)
    model.meta["sweep_fit"] = {
        **sweep_fit.meta["fit"],
        "alpha": sweep_fit.alpha, "alpha_c": sweep_fit.alpha_c,
        "alpha_hop": sweep_fit.alpha_hop, "beta": sweep_fit.beta,
        "local_rate": sweep_fit.local_rate,
    }
    profile_path = model.save(os.path.join(args.profile_dir,
                                           f"{machine}.json"))
    r2 = sweep_fit.meta["fit"]["r2"]
    print(f"# wrote {profile_path}  (sweep-regression diagnostic R²={r2:.3f})")

    # --- predicted vs measured crossovers (Fig. 1 analogue) ---------------
    crossings = {}
    for p in args.p:
        exps = exps_override or EXPS.get(p, EXPS[256])
        meas_rows, meas_x = measured_crossovers(cells, p)
        pred_rows, pred_x = predicted_crossovers(p, exps, model)
        crossings[str(p)] = {
            "measured_winners": meas_rows, "measured_crossovers": meas_x,
            "predicted_winners": pred_rows, "predicted_crossovers": pred_x,
        }
        print(f"# p={p} measured : " +
              " ".join(f"2^{e}:{w}" for e, w in meas_rows))
        print(f"# p={p} predicted: " +
              " ".join(f"2^{e}:{w}" for e, w in pred_rows))

    bench = {}
    for c in cells + local_cells:
        bench.setdefault(str(c["p"]), {}).setdefault(
            c["algorithm"], {})[str(c["e"])] = c["us"]
    with open(args.bench_json, "w") as f:
        json.dump({
            "machine": machine,
            "host": platform.node(),
            "p": args.p,
            "cells": cells,
            "profile": {"path": profile_path,
                        "alpha": model.alpha, "alpha_c": model.alpha_c,
                        "alpha_hop": model.alpha_hop, "beta": model.beta,
                        "local_rate": model.local_rate,
                        "partition_rate": model.partition_rate,
                        "alpha_inner": model.alpha_inner,
                        "alpha_c_inner": model.alpha_c_inner,
                        "beta_inner": model.beta_inner,
                        "io_beta": model.io_beta,
                        "overlap": model.overlap},
            "sweep_fit": model.meta["sweep_fit"],
            "crossovers": crossings,
            "bench": bench,
        }, f, indent=2, sort_keys=True)
    print(f"# wrote {args.bench_json}")

    if args.experiments:
        path = write_experiments(args.experiments, model)
        print(f"# wrote {path} (profile: {model.name})")
    return 0


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
