"""Paper Fig. 2: robust vs non-robust variants.

2a  RQuick / NTB-Quick (no shuffle, no tie-break)
2b  RAMS / NTB-AMS (no sample tie-breaking)
2d  RAMS / SSort and NS-SSort (oracle splitters)

`derived` reports the ratio (or the failure mode of the non-robust
variant: OVERFLOW(n) — our static-capacity analogue of the paper's
deadlocks/crashes).
"""
import numpy as np

from repro.core.api import SortConfig, psort
from repro.data.distributions import generate_instance

from common import emit, timeit

P = 8


def run_pair(tag, inst, n, robust_algo, nonrobust_algo, robust_kw=None,
             nonrobust_kw=None):
    x = generate_instance(inst, P, n).astype(np.int32)
    cfg_r = SortConfig(p=P, algorithm=robust_algo,
                       algo_kw=robust_kw or {})
    us_r = timeit(lambda: np.asarray(psort(x, config=cfg_r)))
    _, info_r = psort(x, config=cfg_r, return_info=True)
    assert info_r["overflow"] == 0, (tag, inst, n)
    try:
        cfg_n = SortConfig(p=P, algorithm=nonrobust_algo,
                           algo_kw=nonrobust_kw or {})
        _, info_n = psort(x, config=cfg_n, return_info=True)
        if info_n["overflow"] > 0:
            emit(f"{tag}/{inst}/n{n}", us_r,
                 f"nonrobust OVERFLOW({info_n['overflow']})")
            return
        us_n = timeit(lambda: np.asarray(psort(x, config=cfg_n)))
        emit(f"{tag}/{inst}/n{n}", us_r, f"ratio={us_r / us_n:.3f}")
    except Exception as e:   # noqa: BLE001
        emit(f"{tag}/{inst}/n{n}", us_r, f"nonrobust FAIL:{type(e).__name__}")


def main():
    for inst in ["Uniform", "Staggered", "DeterDupl", "BucketSorted",
                 "Mirrored"]:
        for n in [64, 1024, 8192]:
            run_pair("fig2a_rquick_vs_ntb", inst, n, "rquick", "ntb-quick")
    for inst in ["Uniform", "DeterDupl", "BucketSorted"]:
        for n in [1024, 8192]:
            run_pair("fig2b_rams_vs_ntb", inst, n, "rams", "ntb-ams")
    for inst in ["Uniform", "AllToOne", "Zero"]:
        for n in [1024, 8192]:
            run_pair("fig2d_rams_vs_ssort", inst, n, "rams", "ssort")


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main()
