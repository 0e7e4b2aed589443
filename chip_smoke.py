"""Smoke test: psort and the query service end to end on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip shard_map path only

Runs in one process, with the local kernels at their TPU default, through
the entry points a user calls.  Keys come from ``repro.data.distributions``
under ``--seed``.  Every answer is checked against numpy; any failed check
or error ends the run with a non-zero exit.  Without a TPU the script fails
at once and prints no result.

One chip:

  K  ``local_sort`` compiled at the phase-B RAMS shard: it must contain a
     Mosaic kernel (``tpu_custom_call``) and sort a random shard exactly.
  A  ``psort(keys, config=SortConfig())`` — shard_map over the one device,
     n = 2^24 u32 Uniform keys.
  B  ``backend="sim"`` at p = 64: gatherm (n = 4), rfis (n = 4096), rquick
     (n = 2^20, u32, and once u64) and ``algorithm="auto"`` at n = 2^24,
     which must pick rams — each on Uniform, DeterDupl and AllToOne keys.
  C  a ``SortService`` over the resident 2^24 keys at p = 64 answering a
     few dozen mixed top_k / percentile / rank_of_key / range_query
     requests.

``--chips 4``: shard_map over ``default_mesh(4)`` — auto at n = 2^20 (rams,
2^18 keys per chip) and rquick at n = 2^26 (2^24 per chip) — and each
device's peak memory.

The first calls of all phases are issued together from a thread pool, so
their compilations overlap; the warm calls then run one at a time.  The
times printed are smoke wall clock, not benchmark numbers.  The last line
of output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"
P_SIM = 64
N_MID = 1 << 20                 # rquick's regime at p = 64
N_BIG = 1 << 24                 # rams' regime at p = 64 (2^18 keys per PE)
N_CHIP = 1 << 24                # keys per chip for the four-chip rquick
DISTS = ("Uniform", "DeterDupl", "AllToOne")


def check(ok, what: str):
    if not ok:
        raise AssertionError(f"smoke check failed: {what}")


def timed(fn):
    t = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t


def first_calls(fns):
    """Run each phase's first call at once, so that their compilations
    overlap; returns {name: (result, seconds)}."""
    with ThreadPoolExecutor(len(fns)) as ex:
        futs = {name: ex.submit(timed, fn) for name, fn in fns.items()}
        return {name: f.result() for name, f in futs.items()}


def u32_keys(dist: str, p: int, n: int, seed: int) -> np.ndarray:
    from repro.data.distributions import generate_instance
    return generate_instance(dist, p, n, seed=seed).astype(np.uint32)


def u64_keys(dist: str, p: int, n: int, seed: int) -> np.ndarray:
    """Full-width 64-bit keys from two 32-bit draws of the distribution."""
    from repro.data.distributions import generate_instance
    hi = generate_instance(dist, p, n, seed=seed).astype(np.uint64)
    lo = generate_instance(dist, p, n, seed=seed + 1).astype(np.uint64)
    return (hi << np.uint64(32)) | lo


def sorted_check(name: str, keys: np.ndarray, out, info=None,
                 algorithm=None):
    out = np.asarray(out)
    check(out.dtype == keys.dtype and np.array_equal(out, np.sort(keys)),
          f"{name}: output != np.sort(keys)")
    if info is not None:
        check(info["overflow"] == 0, f"{name}: overflow {info['overflow']}")
        perm = np.asarray(info["perm"]).astype(np.int64)
        check(np.array_equal(np.sort(perm), np.arange(keys.size)),
              f"{name}: perm is not a permutation")
        check(np.array_equal(keys[perm], out), f"{name}: keys[perm] != out")
        if algorithm is not None:
            check(info["algorithm"] == algorithm,
                  f"{name}: picked {info['algorithm']}, expected {algorithm}")


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def kernel_check(seed: int) -> str:
    """Phase K: the local sort at the phase-B RAMS shard is a Mosaic
    kernel on the chip, and it sorts exactly."""
    import jax
    import jax.numpy as jnp
    from repro.core.types import SortShard, local_kernels, local_sort

    pol = local_kernels()
    check(pol.sort and pol.partition, f"TPU kernel default is {pol}")
    cap = 2 * (N_BIG // P_SIM)                # psort's per-PE capacity

    @jax.jit
    def sort_shard(keys, idx, count):
        out = local_sort(SortShard(keys, {"idx": idx}, count))
        return out.keys, out.vals["idx"]

    keys = u32_keys("Uniform", 1, cap, seed)
    idx = np.arange(cap, dtype=np.uint32)
    count = np.int32(cap - 12345)
    compiled = sort_shard.lower(keys, idx, count).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "local_sort has no Mosaic kernel")
    ks, vs = (np.asarray(a) for a in compiled(keys, idx, count))
    want = np.sort(keys[:count])
    check(np.array_equal(ks[:count], want), "kernel local_sort != np.sort")
    check(np.all(ks[count:] == np.uint32(0xFFFFFFFF)), "pad tail moved")
    check(np.array_equal(keys[vs[:count]], want), "kernel payload mismatch")
    return str(pol)


def phase_a(keys):
    from repro.core import SortConfig, psort
    return psort(keys, config=SortConfig())


def phase_b_cells(seed: int):
    """(label, keys per distribution, config, expected algorithm)."""
    from repro.core import SortConfig
    cells = []
    for algo, n in (("gatherm", 4), ("rfis", 4096), ("rquick", N_MID),
                    ("auto", N_BIG)):
        cells.append((f"{algo} n={n} u32",
                      [u32_keys(d, P_SIM, n, seed) for d in DISTS],
                      SortConfig(p=P_SIM, algorithm=algo, backend="sim"),
                      "rams" if algo == "auto" else algo))
    cells.append((f"rquick n={N_MID} u64",
                  [u64_keys("Uniform", P_SIM, N_MID, seed)],
                  SortConfig(p=P_SIM, algorithm="rquick", backend="sim"),
                  "rquick"))
    return cells


def service_requests(keys: np.ndarray, seed: int):
    rng = np.random.default_rng(seed)
    pool = keys[rng.integers(0, keys.size, size=16)]
    reqs = [("top_k", int(k)) for k in rng.integers(1, 2000, size=10)]
    reqs += [("percentile", float(q)) for q in rng.uniform(0, 100, size=8)]
    reqs += [("rank_of_key", int(k)) for k in pool[:8]]
    reqs += [("rank_of_key", int(k)) for k in rng.integers(0, 1 << 32, 2)]
    reqs += [("range_query", (int(min(a, b)), int(max(a, b))))
             for a, b in zip(pool[8:], rng.permutation(pool)[8:])]
    order = rng.permutation(len(reqs))
    return [reqs[i] for i in order]


def phase_c(keys: np.ndarray, reqs):
    from repro.core import SortConfig
    from repro.launch.sort_serve import SortService
    svc = SortService(keys, config=SortConfig(p=P_SIM, backend="sim"))
    for kind, arg in reqs:
        svc.submit(kind, arg)
    return svc.drain()


def service_check(keys: np.ndarray, done):
    s = np.sort(keys)
    n = s.size
    for r in done:
        kind, arg, got = r.request.kind, r.request.arg, r.value
        if kind == "top_k":
            ok = np.array_equal(np.asarray(got), s[n - arg:])
        elif kind == "percentile":
            ok = got == s[int(np.floor(arg / 100.0 * (n - 1)))]
        elif kind == "rank_of_key":
            k = np.uint32(arg)
            ok = tuple(got) == (np.searchsorted(s, k, "left"),
                                np.searchsorted(s, k, "right"))
        else:
            lo, hi = np.uint32(arg[0]), np.uint32(arg[1])
            ok = got == max(np.searchsorted(s, hi, "left")
                            - np.searchsorted(s, lo, "left"), 0)
        check(ok, f"service {kind}({arg}) = {got}")


def one_chip(seed: int):
    from repro.core import psort

    print(f"[K] local kernels: {kernel_check(seed)}; local_sort at the "
          f"{2 * N_BIG // P_SIM}-word RAMS shard compiles to a Mosaic kernel "
          f"and sorts exactly", flush=True)
    big = u32_keys("Uniform", 1, N_BIG, seed)
    cells = phase_b_cells(seed)
    resident = cells[3][1][0]                  # the 2^24 Uniform keys
    reqs = service_requests(resident, seed)

    first = {"A": lambda: phase_a(big),
             "C": lambda: phase_c(resident, reqs)}
    for label, keys, cfg, _ in cells:
        first[label] = (lambda k=keys[0], c=cfg:
                        psort(k, config=c, return_info=True))
    res = first_calls(first)

    out, t1 = res["A"]
    sorted_check("A", big, out)
    _, t2 = timed(lambda: sorted_check("A", big, phase_a(big)))
    print(f"[A] psort(SortConfig()) shard_map p=1 n={N_BIG} u32: first call "
          f"{t1:.3f}s, warm {t2:.3f}s", flush=True)

    for label, keys, cfg, algo in cells:
        (out, info), t1 = res[label]
        sorted_check(f"B {label} {DISTS[0]}", keys[0], out, info, algo)
        warm = []                   # a cell of one distribution repeats it
        for dist, k in list(zip(DISTS, keys))[1:] or [(DISTS[0], keys[0])]:
            (o, i), t = timed(lambda: psort(k, config=cfg, return_info=True))
            sorted_check(f"B {label} {dist}", k, o, i, algo)
            warm.append(t)
        print(f"[B] sim p={P_SIM} {label} -> {info['algorithm']} over "
              f"{', '.join(DISTS[:len(keys)])}: first call {t1:.3f}s, warm "
              f"{', '.join(f'{w:.3f}s' for w in warm)}", flush=True)

    done, t1 = res["C"]
    check(len(done) == len(reqs), "service dropped requests")
    service_check(resident, done)
    done2, t2 = timed(lambda: phase_c(resident, reqs))
    service_check(resident, done2)
    paths = sorted({f"{r.request.kind}:{r.path}" for r in done2})
    print(f"[C] SortService sim p={P_SIM} n={N_BIG}: {len(reqs)} requests "
          f"({', '.join(paths)}): first {t1:.3f}s, warm {t2:.3f}s",
          flush=True)


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------


def four_chips(seed: int, devices):
    from repro.core import SortConfig, psort
    from repro.core.api import default_mesh

    mesh = default_mesh(4)
    cells = [(algo, n, want, u32_keys("Uniform", 4, n, seed),
              SortConfig(mesh=mesh, algorithm=algo))
             for algo, n, want in (("auto", N_MID, "rams"),
                                   ("rquick", 4 * N_CHIP, "rquick"))]
    res = first_calls({algo: (lambda k=keys, c=cfg:
                              psort(k, config=c, return_info=True))
                       for algo, _, _, keys, cfg in cells})
    for algo, n, want, keys, cfg in cells:
        (out, info), t1 = res[algo]
        sorted_check(f"4chip {algo} n={n}", keys, out, info, want)
        (out, info), t2 = timed(lambda: psort(keys, config=cfg,
                                              return_info=True))
        sorted_check(f"4chip {algo} n={n}", keys, out, info, want)
        print(f"[4] shard_map p=4 {algo} n={n} -> {info['algorithm']} "
              f"({n // 4} keys per chip): first call {t1:.3f}s, warm "
              f"{t2:.3f}s", flush=True)
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print(f"[4] peak bytes in use per device: {peaks}", flush=True)
    check(max(peaks) <= 2 * min(peaks), "work is not spread over the chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is not at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 1
    from repro.runtime.compile_cache import use_compile_cache
    cache = use_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{cache}; times below are smoke wall clock, not benchmark "
          f"numbers", flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed, devices[:4])
    else:
        one_chip(args.seed)
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
