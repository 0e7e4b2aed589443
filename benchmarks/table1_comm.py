"""Paper Table I: latency (startup count) and communication volume per PE,
measured two independent ways against the asymptotic prediction:

  * *compiled HLO* of each algorithm (collective ops counted with the
    trip-count-aware analyzer), and
  * the *counted collective trace* (``repro.core.api.trace_collectives``
    — the call-site instrumentation ``benchmarks/calibrate.py`` fits the
    machine profile from).

derived = "colls=<count> cnt=<counted> (pred O(<latency>)),
           wire=<bytes/PE> B (pred O(<volume>) = <words> words)"
"""
import numpy as np

import jax
from repro.core import types as ct
from repro.core.api import (SortConfig, _algorithm_fn, default_mesh,
                            trace_collectives)
from repro.launch import hlo_cost
from jax.sharding import PartitionSpec as P

from common import emit

P_DEV = 8
NPP = 256


def lower_algo(algorithm):
    mesh = default_mesh(P_DEV)
    fn = _algorithm_fn(algorithm)

    def body(keys):
        sh = ct.make_shard(keys[0], capacity=2 * NPP)
        out, ovf = fn(sh, "sort", P_DEV)
        return out.keys[None, :2 * NPP], ovf[None]

    keys = jax.ShapeDtypeStruct((P_DEV, NPP), jax.numpy.uint32)
    with mesh:
        c = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("sort"),),
                                  out_specs=(P("sort"), P("sort")),
                                  check_vma=False)
                    ).lower(keys).compile()
    return hlo_cost.analyze(c.as_text())


PRED = {   # Table I rows: (latency O(·), comm volume O(·) in words/PE)
    "gatherm": ("log p", "n", lambda n, p: n),
    "allgatherm": ("log p", "n", lambda n, p: n),
    "rfis": ("log p", "n/sqrt(p)", lambda n, p: n / np.sqrt(p)),
    "rquick": ("log^2 p", "(n/p)log p", lambda n, p: n / p * np.log2(p)),
    "rams": ("k log_k p", "(n/p)log_k p", lambda n, p: 2 * n / p),
    "bitonic": ("log^2 p", "(n/p)log^2 p",
                lambda n, p: n / p * np.log2(p) ** 2),
    "ssort": (">= p", ">= n/p", lambda n, p: n / p),
}


def main():
    n = NPP * P_DEV
    for algo, (lat, vol, vol_fn) in PRED.items():
        try:
            a = lower_algo(algo)
        except Exception as e:   # noqa: BLE001
            emit(f"table1/{algo}", float("nan"), f"FAIL:{type(e).__name__}")
            continue
        colls = sum(a["collective_counts"].values())
        wire = sum(a["collective_bytes"].values())
        pred_words = vol_fn(n, P_DEV)
        try:
            tr = trace_collectives(n, SortConfig(p=P_DEV, algorithm=algo))
            counted = f"cnt={tr.launches}/{tr.wire_bytes()}B"
        except Exception as e:   # noqa: BLE001
            counted = f"cnt=FAIL:{type(e).__name__}"
        emit(f"table1/{algo}", 0.0,
             f"colls={colls:.0f} {counted} (pred O({lat})) wire={wire:.0f}B/PE "
             f"(pred O({vol})={pred_words:.0f}w={4 * pred_words:.0f}B)")


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main()
