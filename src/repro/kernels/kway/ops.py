"""Jitted wrapper for the k-way classifier: pads to the kernel block size,
falls back to the jnp oracle for tiny inputs."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import kway
from .kway import BLOCK_R, LANES

_BLOCK = BLOCK_R * LANES


def kway_classify(keys, ties, s_keys, s_ties, *, n_buckets: int,
                  interpret=None, use_kernel: bool = True):
    """Classify u32 (key, tie) pairs against (NB-1,) lex splitters."""
    C = keys.shape[0]
    if not use_kernel or C < _BLOCK or s_keys.shape[0] == 0:
        from . import ref
        return ref.kway_classify_ref(keys, ties, s_keys, s_ties,
                                     n_buckets=n_buckets)
    pad = (-C) % _BLOCK
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), np.uint32(0xFFFFFFFF),
                                               keys.dtype)])
        ties = jnp.concatenate([ties, jnp.full((pad,), np.uint32(0xFFFFFFFF),
                                               ties.dtype)])
    bucket, hist = kway.kway_classify(keys, ties, s_keys, s_ties,
                                      n_buckets=n_buckets, interpret=interpret)
    if pad:
        # Padded entries are all-ones (key, tie) pairs: every splitter
        # compares <= them, so they land in bucket len(s_keys) — the last
        # bucket only when the caller supplies exactly n_buckets-1
        # splitters.  Subtract them where they actually landed, and clamp:
        # real all-ones elements share that bucket, and the count must
        # never go negative when pad >= the bucket's true population.
        bucket = bucket[:C]
        hist = hist.at[min(s_keys.shape[0], n_buckets - 1)].add(-pad)
        hist = jnp.maximum(hist, 0)
    return bucket, hist
