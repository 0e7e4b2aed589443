"""DeterDupl: only log2(p) distinct keys, 0 to log2(p) - 1."""
import numpy as np

from bench.gen import draw, rng


def gen(i, p, m, seed, bits):
    k = max(1, int(np.log2(max(p, 2))))
    return draw(rng(seed, i), 0, k, m, bits)
