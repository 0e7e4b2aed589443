"""Uniform: independent keys drawn uniformly from [0, 2^bits)."""
from bench.gen import draw, rng


def gen(i, p, m, seed, bits):
    return draw(rng(seed, i), 0, 2 ** bits, m, bits)
