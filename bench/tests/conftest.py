"""The benchmark's own tests: on the CPU, at small sizes.

    pytest bench/tests

Four emulated CPU devices stand in for a four-chip host; this has to be
set before JAX starts its backend.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
