"""Time ``local_sort`` alone at the shard shapes of the benchmark's cells.

    python tools/local_sort_sweep.py [--calls 25] [--out sweep.json]

For each (capacity, key width, valid count) below, one jitted
``local_sort`` of a shard with one u32 payload plane, warmed up, then
``--calls`` calls each timed to ``block_until_ready``; prints the median
and the spread in ms.  Implementations:

* ``xla``: the default, one stable ``lax.sort`` of (keys, payload);
* ``kernel``: the Pallas bitonic path (4-byte keys only), as under
  ``REPRO_LOCAL_KERNELS=sort``;
* ``argsort``: a stable argsort of the keys, then one gather per plane.

Every answer is checked against the stable NumPy sort of the same shard.
Meant for a TPU; on another backend the kernel runs in the interpreter, so
use small ``--scale`` there.
"""
import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.types import (LocalKernelPolicy, SortShard,  # noqa: E402
                              local_sort, set_local_kernels)

# (what runs it, capacity, key dtype, valid count)
SHAPES = [
    ("lg16 p=1", 1 << 17, "uint32", 1 << 16),
    ("lg20 shuffle", 1057292, "uint32", 1 << 18),
    ("lg20 level", 2109464, "uint32", 1 << 18),
    ("lg24 p=1", 1 << 25, "uint32", 1 << 24),
    ("u64 lg20 shuffle", 1057292, "uint64", 1 << 18),
    ("u64 lg20 level", 2109464, "uint64", 1 << 18),
]


def _argsort_gather(shard):
    keys = jnp.where(shard.valid_mask(), shard.keys, shard.pad)
    order = jnp.argsort(keys, stable=True)
    return shard.replace(keys=keys[order],
                         vals={k: v[order] for k, v in shard.vals.items()})


def _program(impl):
    """The jitted body; the kernel policy is read when it is traced."""
    def body(keys, idx, count):
        shard = SortShard(keys, {"idx": idx}, count)
        out = _argsort_gather(shard) if impl == "argsort" \
            else local_sort(shard)
        return out.keys, out.vals["idx"]
    return jax.jit(body)


def measure(impl, cap, dtype, count, calls, seed):
    k0, k1 = jax.random.split(jax.random.key(seed))
    keys = jax.random.bits(k0, (cap,), jnp.dtype(dtype))
    idx = jax.random.permutation(k1, cap).astype(jnp.uint32)
    count = jnp.int32(count)
    prev = set_local_kernels(LocalKernelPolicy(sort=impl == "kernel"))
    try:
        t0 = time.perf_counter()
        fn = _program(impl).lower(keys, idx, count).compile()
        compile_s = time.perf_counter() - t0
    finally:
        set_local_kernels(prev)
    out = jax.block_until_ready(fn(keys, idx, count))
    ms = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(keys, idx, count))
        ms.append(1e3 * (time.perf_counter() - t0))
    k, v = (np.asarray(a) for a in out)
    hk, hv, c = np.asarray(keys), np.asarray(idx), int(count)
    want = np.sort(hk[:c], kind="stable")
    pos = np.empty(cap, np.int64)
    pos[hv] = np.arange(cap)            # idx is a permutation of the slots
    ok = bool(np.array_equal(k[:c], want)
              and np.all(k[c:] == np.iinfo(hk.dtype).max)
              and np.array_equal(np.sort(v[:c]), np.sort(hv[:c]))
              and np.array_equal(hk[pos[v[:c]]], want))
    if impl != "kernel":        # the stable paths give the stable payload
        order = np.argsort(hk[:c], kind="stable")
        ok = ok and bool(np.array_equal(v[:c], hv[:c][order]))
    q = statistics.quantiles(ms, n=4)
    return {"median_ms": statistics.median(ms), "q1_ms": q[0],
            "q3_ms": q[2], "min_ms": min(ms), "compile_s": compile_s,
            "correct": ok}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--calls", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=1,
                    help="divide every capacity and count by this")
    ap.add_argument("--out", default=None, help="also write the rows here")
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    rows = []
    for what, cap, dtype, count in SHAPES:
        cap, count = cap // a.scale, count // a.scale
        impls = ("argsort", "xla") if dtype == "uint64" \
            else ("kernel", "xla", "argsort")
        for impl in impls:
            r = dict(shape=what, capacity=cap, key=dtype, count=count,
                     impl=impl, **measure(impl, cap, dtype, count, a.calls,
                                          a.seed))
            rows.append(r)
            print(json.dumps(r), flush=True)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
