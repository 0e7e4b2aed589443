"""Mixed-query serving throughput benchmark → ``BENCH_serve.json``.

Measures, per p, the wall-clock of answering a query micro-batch two
ways — the sort-free selection fast path of ``core/queries.py`` versus
sorting first with ``psort`` and indexing — plus the counting queries and
a mixed-stream :class:`repro.launch.sort_serve.SortService` drain.  Cells
land in the same ``bench[p][name][e]`` shape as ``BENCH_calibrate.json``
(e = log2(n/p), µs per cell) and are gated by ``tools/check_bench.py``
in the CI ``serve`` lane (with ``--fail-on-dropped``: the committed
baseline's cells must all be produced, every run).

The headline acceptance cells: ``serve/top_k`` and ``serve/percentile``
must beat their ``*_fullsort`` counterparts at p ∈ {64, 256} — the
selection path's device work is polylog in n while the sort's is Ω(n/p).

  PYTHONPATH=src python benchmarks/serve_bench.py --smoke \
      --bench-json BENCH_fresh_serve.json
  PYTHONPATH=src python benchmarks/serve_bench.py   # full iters, CI grid

``--smoke`` only drops the timed iterations to 1 — the (p, e) cell grid
is identical, so smoke runs still produce every gated cell.
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

import jax

from repro.core import SortConfig, psort
from repro.core.queries import (percentile, range_query, rank_of_key,
                                shard_data, top_k)
from repro.launch.sort_serve import SortService

BATCH = 8           # queries per micro-batch in the per-kind cells
MIX_QUERIES = 24    # stream length of the serve/mixed cell


def _best_us(fn, iters: int, reps: int = 1) -> float:
    """Fastest observed wall-clock of ``fn`` in µs — min over ``iters``
    measurements of a ``reps``-call chain.  Min, not median: the gate
    compares ratios across runner generations, and the minimum is the
    measurement least contaminated by scheduler noise.  ``reps`` chains
    calls inside one measurement so sub-millisecond dispatch-bound cells
    (the counting queries) average out per-call jitter."""
    fn()                                          # compile + warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        ts.append(time.perf_counter() - t0)
    return float(min(ts)) / reps * 1e6


def bench_p(p: int, e: int, iters: int, seed: int = 0,
            cheap_iters: int = 3):
    """All serve cells for one (p, e): returns {name: us}.

    ``iters`` drives the heavy full-sort cells (the expensive part a
    smoke run cuts to 1); the millisecond-scale selection/counting cells
    always run ``cheap_iters`` measurements — they cost nothing and the
    gate needs the extra samples for a stable minimum."""
    n = p << e
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 1 << 32, size=n).astype(np.int64)
    data = shard_data(keys, p)
    ks = np.linspace(1, min(64, n), BATCH).astype(np.int64)
    qs = np.linspace(0.0, 100.0, BATCH)
    probe = keys[rng.integers(0, n, size=BATCH)]
    lo = np.minimum(probe, keys[rng.integers(0, n, size=BATCH)])
    hi = np.maximum(probe, keys[rng.integers(0, n, size=BATCH)])

    def sorted_now():
        # the fullsort path's per-query-batch cost: sort, then answer
        # locally (post-warmup, so the psort jit cache is hot — this
        # times device work, not tracing).  rquick is pinned because it
        # is the fastest full sort at these (n, p) on the sim backend —
        # the selection cells must beat the *best* sorting comparator,
        # not whatever the regime model happens to pick.
        return np.asarray(jax.block_until_ready(
            psort(keys, config=SortConfig(p=p, algorithm="rquick",
                                          backend="sim"))))

    def topk_fullsort():
        s = sorted_now()                   # one sort answers the batch
        return [s[n - k:] for k in ks]

    def pct_fullsort():
        s = sorted_now()
        return s[np.floor(qs / 100.0 * (n - 1)).astype(np.int64)]

    ic = max(iters, cheap_iters)
    out = {
        "serve/top_k": _best_us(lambda: top_k(data, ks), ic, reps=3),
        "serve/top_k_fullsort": _best_us(topk_fullsort, iters),
        "serve/percentile": _best_us(lambda: percentile(data, qs), ic,
                                     reps=3),
        "serve/percentile_fullsort": _best_us(pct_fullsort, iters),
        "serve/rank_of_key": _best_us(
            lambda: rank_of_key(data, probe), ic, reps=10),
        "serve/range_query": _best_us(
            lambda: range_query(data, lo, hi), ic, reps=10),
        "serve/sort": _best_us(sorted_now, iters),
    }

    def mixed():
        svc = SortService(keys, p, backend="sim", policy="selection")
        r = np.random.default_rng(seed + 1)
        for _ in range(MIX_QUERIES):
            kind = ("top_k", "percentile", "rank_of_key",
                    "range_query")[r.integers(4)]
            arg = {"top_k": int(ks[r.integers(BATCH)]),
                   "percentile": float(qs[r.integers(BATCH)]),
                   "rank_of_key": int(probe[r.integers(BATCH)]),
                   "range_query": (int(lo[r.integers(BATCH)]),
                                   int(hi[r.integers(BATCH)]))}[kind]
            svc.submit(kind, arg)
        svc.drain()

    out["serve/mixed"] = _best_us(mixed, ic) / MIX_QUERIES
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--p", type=int, nargs="+", default=[64, 256])
    ap.add_argument("--e", type=int, nargs="+", default=[6],
                    help="log2(n/p) per cell")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="1 timed iteration of the heavy full-sort cells "
                         "(same cell grid; cheap cells keep 3 iterations)")
    ap.add_argument("--machine", default="local")
    ap.add_argument("--bench-json", default="BENCH_serve.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    iters = 1 if args.smoke else args.iters

    bench = {}
    for p in args.p:
        for e in args.e:
            cells = bench_p(p, e, iters, seed=args.seed)
            for name, us in cells.items():
                bench.setdefault(str(p), {}).setdefault(name, {})[str(e)] \
                    = us
            print(f"# p={p} e={e}: " + "  ".join(
                f"{k.split('/')[1]}={v:.0f}us" for k, v in cells.items()))
            for kind in ("top_k", "percentile"):
                sel = cells[f"serve/{kind}"]
                full = cells[f"serve/{kind}_fullsort"]
                tag = "beats" if sel < full else "LOSES TO"
                print(f"#   {kind}: selection {tag} fullsort "
                      f"({sel:.0f}us vs {full:.0f}us, "
                      f"{full / max(sel, 1e-9):.1f}x)")

    with open(args.bench_json, "w") as f:
        json.dump({"machine": args.machine, "host": platform.node(),
                   "p": args.p, "bench": bench}, f, indent=2,
                  sort_keys=True)
    print(f"# wrote {args.bench_json}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main()
