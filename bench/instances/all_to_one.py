"""AllToOne: PE i draws from the i-th range counted from the top of
[p, 2^bits), and its last key is p - i, so that a naive k-way sample sort
sends min(p, n/p) messages to PE 0 on its first level."""
from bench.gen import draw, rng


def gen(i, p, m, seed, bits):
    top = 2 ** bits
    lo = min(p + (p - i) * ((top - p) // p), top - 2)
    hi = min(p + (p - i + 1) * ((top - p) // p), top - 1)
    out = draw(rng(seed, i), lo, max(hi, lo + 1), m, bits)
    if m:
        out[-1] = p - i
    return out
