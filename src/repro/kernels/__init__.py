"""Pallas TPU kernels for the paper's compute hot spots.

bitonic/   — local sort + bitonic merge networks (VMEM-resident, VPU-only)
kway/      — Super Scalar Sample Sort k-way classifier with tie-breaking
partition/ — fused classify + histogram + in-bucket rank: the
             (bucket, send_pos, hist) triple feeding every all_to_all
             (what rams/samplesort/rquick actually call)

Each kernel ships ops.py (jit wrapper + fallback) and ref.py (pure-jnp
oracle); tests sweep shapes × dtypes against the oracle in interpret mode.
Which kernels run is a policy decision: ``repro.core.types.local_kernels``
(``REPRO_LOCAL_KERNELS`` — default on for TPU backends, off elsewhere).
How a kernel that runs is executed is decided here, in one place:
:func:`interpret_mode`.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(override: Optional[bool] = None) -> bool:
    """Run Pallas kernels in the interpreter?  Exactly when the default JAX
    backend is not a TPU, resolved at trace time; on a TPU every kernel is
    compiled by Mosaic.  ``override`` (a kernel's ``interpret=`` argument)
    wins when given — compile-only tests pass ``False`` to lower for a
    described TPU from a CPU host."""
    if override is not None:
        return override
    return jax.default_backend() != "tpu"
