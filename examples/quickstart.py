"""Quickstart: robust distributed sorting with repro.core.psort.

Sorts every paper input instance with the auto-selected algorithm over
every device JAX sees — the chips of a TPU host, or 8 emulated devices on a
CPU — and prints the selection + balance.

  PYTHONPATH=src python examples/quickstart.py
"""
import os

# only the host (CPU) platform reads this; a TPU keeps its real chip count
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                                          # noqa: E402
import numpy as np                                  # noqa: E402

from repro.core import SortConfig, psort, select_algorithm  # noqa: E402
from repro.data.distributions import INSTANCES, generate_instance  # noqa: E402


def main():
    P = jax.device_count()
    print(f"shard_map over {P} {jax.devices()[0].platform} device(s)")
    print(f"{'instance':14s} {'n':>7s} {'algorithm':10s} {'sorted':6s} "
          f"{'balance':7s} {'overflow'}")
    for inst in sorted(INSTANCES):
        for n in (4, 1024, 16384):
            x = generate_instance(inst, P, n).astype(np.int32)
            out, info = psort(x, config=SortConfig(p=P, algorithm="auto"),
                              return_info=True)
            ok = bool((np.asarray(out) == np.sort(x)).all())
            print(f"{inst:14s} {n:7d} {info['algorithm']:10s} {str(ok):6s} "
                  f"{info['balance']:7.2f} {info['overflow']}")
            assert ok and info["overflow"] == 0

    # high emulated PE counts: the sim backend is not capped by devices
    x = generate_instance("Staggered", 128, 128 * 32).astype(np.int32)
    out = psort(x, config=SortConfig(p=128, algorithm="rquick",
                                     backend="sim"))
    ok = bool((np.asarray(out) == np.sort(x)).all())
    print(f"\nsim backend: p=128 rquick sorted={ok}")
    assert ok

    # the paper's headline: algorithm choice depends on n/p
    print("\nAuto-selection regimes at p=262144 (paper Fig. 1 structure):")
    for e in (-8, -2, 0, 4, 10, 16, 22):
        n = max(1, int(262144 * 2.0 ** e))
        print(f"  n/p = 2^{e:>3d}  →  {select_algorithm(n, 262144)}")


if __name__ == "__main__":
    main()
