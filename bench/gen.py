"""Input instances of the benchmark, made from a seed on the host.

The instances are the paper's (Axtmann & Sanders, "Robust Massively
Parallel Sorting", arXiv:1606.08766, §VII, after Helman et al.), kept here
so that the yardstick does not move when the program's own generators
change.  Each is a file of its own, ``bench/instances/<name>.py``, found by
the name a traffic mix gives it, and defines ``gen(i, p, m, seed, bits)``:
PE ``i``'s local input of ``m`` keys in [0, 2^bits), where ``bits`` is the
width of the configuration's key type.  A new instance is a new file.
:func:`inputs` forms each global array (PE-major) in the key type.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def module(path: Path):
    """The Python file ``path``, loaded as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        f"bench.{path.parent.name}.{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rng(seed: int, i: int):
    return np.random.default_rng((seed * 1_000_003 + i) & 0x7FFFFFFF)


def draw(r, lo: int, hi: int, m: int, bits: int) -> np.ndarray:
    """``m`` integers in [lo, hi); int64 for keys of up to 32 bits, as the
    paper's instances were first drawn, uint64 above."""
    return r.integers(lo, hi, size=m,
                      dtype=np.int64 if bits <= 32 else np.uint64)


def instance(name: str, p: int, n: int, seed: int = 0, bits: int = 32,
             root: Path = ROOT) -> np.ndarray:
    """Global array (n,) formed from the per-PE generators (PE-major)."""
    gen = module(root / "bench" / "instances" / f"{name}.py").gen
    per = -(-n // p) if n else 0
    parts = []
    left = n
    for i in range(p):
        m = min(per, left)
        parts.append(gen(i, p, m, seed, bits))
        left -= m
    return np.concatenate(parts) if parts else np.zeros(0, np.int64)


def inputs(traffic: dict, p: int, seed: int, dtype=np.uint32,
           root: Path = ROOT) -> list:
    """The distinct inputs a closed-loop cell cycles over, in the key type
    ``dtype``, input ``j`` drawn with the seed ``seed * distinct_inputs +
    j``."""
    dtype = np.dtype(dtype)
    k = int(traffic["distinct_inputs"])
    return [instance(traffic["instance"], p, int(traffic["n"]), seed * k + j,
                     bits=8 * dtype.itemsize, root=root).astype(dtype)
            for j in range(k)]
