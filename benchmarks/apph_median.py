"""Paper App. H / Fig. 4: median-approximation quality, binary k-window
tree (§III-B, ours) vs Dean et al.'s ternary median tree.

2000 trials per size; reports max and variance of the rank error
|r/(n-1) - 1/2| and the fitted c·n^(-γ) envelope exponent.  The paper
finds binary ≈ 1.44·n^-0.39 beating ternary ≈ 2·n^-0.37.
"""
import numpy as np

from common import emit

TRIALS = 2000
K = 16


def binary_tree_median(x, k=K, rng=None):
    """k-window reduction over a balanced binary tree (paper §III-B with
    single-element leaves, the n = p setting of App. H) — vectorized."""
    n = len(x)
    m = 2 ** int(np.floor(np.log2(n)))
    vals = x[:m]
    # m=1 per leaf is odd: the paper's coin flip chooses floor/ceil centering
    # (without it the ±inf fillers drift systematically through the merges)
    coin = rng.integers(0, 2, size=m) if rng is not None \
        else np.zeros(m, np.int64)
    pos = k // 2 - 1 + coin                     # real element's slot
    cols = np.arange(k)[None, :]
    W = np.where(cols < pos[:, None], -np.inf,
                 np.where(cols == pos[:, None], vals[:, None], np.inf))
    while W.shape[0] > 1:
        pairs = W.reshape(-1, 2 * k)
        pairs = np.sort(pairs, axis=1)
        W = pairs[:, k // 2: k // 2 + k]        # middle k of each merge
    coin = int(rng.integers(2)) if rng is not None else 0
    w = W[0]
    v = w[k // 2 - 1 + coin]
    if not np.isfinite(v):                      # coin hit a filler
        v = w[k // 2 - coin]
    return v


def ternary_tree_median(x, rng):
    """Dean et al.: median-of-3 tournament tree."""
    vals = x.copy()
    rng.shuffle(vals)
    m = 3 ** int(np.floor(np.log(len(vals)) / np.log(3)))
    vals = vals[:m]
    while len(vals) > 1:
        vals = np.median(vals.reshape(-1, 3), axis=1)
    return vals[0]


def main():
    rng = np.random.default_rng(0)
    for bits in [8, 10, 12, 14]:
        n = 2 ** bits
        errs_b, errs_t = [], []
        for _ in range(TRIALS // 4):
            x = rng.integers(0, 2**32, size=n).astype(np.float64)
            for est, errs in ((binary_tree_median, errs_b),
                              (ternary_tree_median, errs_t)):
                v = est(x, rng=rng) if est is binary_tree_median \
                    else est(x, rng)
                r = np.searchsorted(np.sort(x), v)
                errs.append(abs(r / (n - 1) - 0.5))
        eb, et = np.array(errs_b), np.array(errs_t)
        emit(f"apph/binary/n{n}", 0.0,
             f"maxerr={eb.max():.4f} var={eb.var():.2e}")
        emit(f"apph/ternary/n{n}", 0.0,
             f"maxerr={et.max():.4f} var={et.var():.2e}")
    # fitted envelope exponents (log-log fit of max error vs n)
    emit("apph/fit", 0.0, _fit(rng))


def _fit(rng):
    ns, bmax, tmax = [], [], []
    for bits in [8, 10, 12, 14]:
        n = 2 ** bits
        eb, et = [], []
        for _ in range(200):
            x = rng.integers(0, 2**32, size=n).astype(np.float64)
            for est, errs in ((binary_tree_median, eb),
                              (ternary_tree_median, et)):
                v = est(x, rng=rng) if est is binary_tree_median else est(x, rng)
                r = np.searchsorted(np.sort(x), v)
                errs.append(abs(r / (n - 1) - 0.5) + 1e-9)
        ns.append(n)
        bmax.append(max(eb))
        tmax.append(max(et))
    gb = -np.polyfit(np.log(ns), np.log(bmax), 1)[0]
    gt = -np.polyfit(np.log(ns), np.log(tmax), 1)[0]
    return f"binary gamma={gb:.3f} ternary gamma={gt:.3f} (paper: 0.39/0.37)"


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main()
