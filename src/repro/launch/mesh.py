"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any import).

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod : (pod=2, data=16, model=16) = 512 chips; the 'pod' axis carries
the slowest collectives (DCN-ish), so only FSDP/grad reductions cross it.
Elastic variants for restore-time resharding are produced by
``make_mesh_shape`` with any axis sizes.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < ndev:
        raise RuntimeError(
            f"need {ndev} devices for mesh {shape}, found {len(devs)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    # jax.make_mesh consumes exactly prod(shape) devices; slice explicitly so
    # the single-pod mesh also works when 512 emulated devices exist.
    return _make_mesh(shape, axes, devs[:ndev])


def _make_mesh(shape, axes, devices):
    # Auto axes: the models place activations with with_sharding_constraint,
    # which the installed jax.make_mesh default (Explicit axes) rejects
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh_shape(shape: Sequence[int], axes: Sequence[str]):
    """Elastic mesh builder (checkpoint restore onto a different topology)."""
    ndev = int(np.prod(shape))
    return _make_mesh(shape, axes, jax.devices()[:ndev])


def make_sort_mesh(p: Optional[int] = None, axis: str = "sort"):
    """1-D mesh for the standalone sorting workloads (configs/sortbench)."""
    devs = jax.devices()
    p = p or len(devs)
    return _make_mesh((p,), (axis,), devs[:p])
