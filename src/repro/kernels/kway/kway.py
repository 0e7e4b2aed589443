"""Pallas TPU kernel: Super Scalar Sample Sort k-way classifier (paper
App. G) with implicit tie-breaking.

Classifies C elements against up to 127 splitters.  GPU SSSS uses a
branchless binary-search tree; on TPU a *compare against every splitter*
is the native formulation: the splitter vector is tiny, so each splitter is
read as a scalar from SMEM and compared with a whole (64, 128) block on the
VPU, with no gathers and no data-dependent control flow — one fused pass
computes bucket ids and the histogram (per-bucket counts accumulated in
VMEM across the grid).

Tie-breaking (paper App. G): an element equal to its bounding splitter's
key is re-compared on (pe, pos) — both sides are u32 planes, so the
lexicographic compare is two vector ops.  Element tie info is generated
locally (own PE id / own position); only the splitters carry communicated
tie-break data, keeping the paper's "no per-element overhead" property.

On a TPU the kernel compiles through Mosaic; elsewhere it runs in the
Pallas interpreter (:func:`repro.kernels.interpret_mode`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels.partition.partition import classify, loop

LANES = 128
BLOCK_R = 64                      # 64×128 elements per grid step
_ZERO = np.int32(0)               # int32 index: a Python 0 is i64 under x64


def _kway_kernel(keys_ref, ties_ref, sk_ref, st_ref, bucket_ref, hist_ref,
                 *, n_buckets: int):
    bucket = classify([keys_ref[...], ties_ref[...]], [sk_ref, st_ref],
                      sk_ref.shape[1])
    bucket_ref[...] = bucket
    lane = jax.lax.broadcasted_iota(jnp.int32, hist_ref.shape, 1)

    def count(b, part):
        # dtype= pins the accumulator: under jax_enable_x64 a plain sum
        # of int32 promotes to int64, which Mosaic cannot lower
        row = jnp.sum((bucket == b).astype(jnp.int32), axis=1, keepdims=True,
                      dtype=jnp.int32)
        n_b = jnp.sum(row, axis=0, keepdims=True, dtype=jnp.int32)  # (1, 1)
        return part + jnp.where(lane == b, n_b, _ZERO)

    part = loop(n_buckets, count, jnp.zeros(hist_ref.shape, jnp.int32))

    @pl.when(pl.program_id(0) == _ZERO)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    hist_ref[...] += part


@functools.partial(jax.jit, static_argnames=("n_buckets", "interpret"))
def kway_classify(keys: jax.Array, ties: jax.Array, s_keys: jax.Array,
                  s_ties: jax.Array, *, n_buckets: int, interpret=None):
    """Returns (bucket_ids (C,), histogram (n_buckets,)).

    C must be a multiple of 64·128 (ops.py pads); splitters are (S,) with
    1 ≤ S ≤ n_buckets - 1.
    """
    C = keys.shape[0]
    R = C // LANES
    assert C % (BLOCK_R * LANES) == 0
    grid = R // BLOCK_R
    width = -(-n_buckets // LANES) * LANES          # lane-aligned histogram
    whole = lambda i: (_ZERO, _ZERO)                # noqa: E731
    blk = pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, _ZERO))
    sspec = pl.BlockSpec((1, s_keys.shape[0]), whole,
                         memory_space=pltpu.SMEM)
    hspec = pl.BlockSpec((1, width), whole)
    bucket, hist = pl.pallas_call(
        functools.partial(_kway_kernel, n_buckets=n_buckets),
        out_shape=(jax.ShapeDtypeStruct((R, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((1, width), jnp.int32)),
        in_specs=[blk, blk, sspec, sspec],
        out_specs=(blk, hspec),
        grid=(grid,), interpret=interpret_mode(interpret),
    )(keys.reshape(R, LANES), ties.reshape(R, LANES),
      s_keys.reshape(1, -1), s_ties.reshape(1, -1))
    return bucket.reshape(C), hist[0, :n_buckets]
