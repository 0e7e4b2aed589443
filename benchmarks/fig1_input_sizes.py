"""Paper Fig. 1: running times of each algorithm across input sizes and
instances.  Measured on p emulated CPU devices (relative regime structure);
`derived` = the v5e α/β-model prediction at p=262144 for the same n/p
(core/selection.py) — the quantity Table I ranks.
"""
import numpy as np

from repro.core.api import SortConfig, psort
from repro.core import selection
from repro.data.distributions import generate_instance

from common import emit, timeit

ALGOS = ["gatherm", "allgatherm", "rfis", "rquick", "rams", "bitonic",
         "ssort"]
INSTANCES = ["Uniform", "BucketSorted", "DeterDupl", "Staggered"]
P = 8
NPP = [0.125, 1, 8, 64, 512, 4096]       # n/p sweep (sparse → large)


def model_time(algo, n, p=262144):
    fn = {
        "gatherm": selection.cost_gatherm, "allgatherm": selection.cost_allgatherm,
        "rfis": selection.cost_rfis, "rquick": selection.cost_rquick,
        "rams": selection.cost_rams, "bitonic": selection.cost_bitonic,
        "ssort": selection.cost_ssort}[algo]
    return fn(max(1, int(n / P * p)), p)


def main():
    for inst in INSTANCES:
        for npp in NPP:
            n = max(0, int(npp * P))
            x = generate_instance(inst, P, n).astype(np.int32)
            for algo in ALGOS:
                if algo in ("rfis", "allgatherm", "gatherm") and npp > 512:
                    # out of the algorithm's regime (RFIS tie-refinement is
                    # O((n/√p)²); gather variants are O(n)-volume) — the
                    # paper's Fig. 1 likewise shows them only while relevant
                    emit(f"fig1/{inst}/npp{npp}/{algo}", float("nan"),
                         "SKIP:out-of-regime")
                    continue
                try:
                    cfg = SortConfig(p=P, algorithm=algo)
                    us = timeit(lambda: np.asarray(psort(x, config=cfg)))
                    ok = (np.asarray(psort(x, config=cfg))
                          == np.sort(x)).all()
                    status = f"{model_time(algo, n):.2e}s@262144" if ok \
                        else "MIS-SORTED"
                except Exception as e:   # noqa: BLE001 — failures are data here
                    us, status = float("nan"), f"FAIL:{type(e).__name__}"
                emit(f"fig1/{inst}/npp{npp}/{algo}", us, status)


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main()
