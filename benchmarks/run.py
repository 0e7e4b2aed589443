"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.py).

The distributed benchmarks need p>1 PEs, so this entry point runs with 8
emulated CPU devices (set before jax import; the 512-device setting stays
confined to the dry-run per the project brief).
"""
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys                                    # noqa: E402
from pathlib import Path                      # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

BENCHES = ["apph_median", "table1_comm", "fig2_robustness",
           "fig1_input_sizes", "moe_dispatch"]


def main() -> None:
    import importlib
    only = sys.argv[1:] or BENCHES
    print("name,us_per_call,derived")
    for name in only:
        mod = importlib.import_module(name)
        print(f"# --- {name} ---", flush=True)
        mod.main()


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main()
