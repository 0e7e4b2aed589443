"""Public API: ``psort`` — distributed sort over a mesh axis.

This is the paper's headline deliverable as a library: one entry point that
covers the entire n/p spectrum by dispatching to GatherM / RFIS / RQuick /
RAMS (``algorithm="auto"``, §IV Table I thresholds re-derived for TPU v5e in
``selection.py``), with robust behavior on all input distributions.

Two layers:
  * ``*_inner`` functions (imported from the algorithm modules) run inside
    ``shard_map`` and compose with other shard_map code (e.g. MoE dispatch);
  * ``psort`` is the host-level convenience wrapper: takes a global array,
    builds the mesh + shard_map, returns the globally sorted array.

Execution backends (``backend=``):
  * ``"shard_map"`` — one shard per device over a mesh axis (production; p
    is capped by the available device count);
  * ``"sim"`` — single-process simulation: the same per-PE body is vmapped
    over a leading PE axis with collectives routed through
    ``repro.core.comm``, lifting the device cap (p = 64–1024 emulated PEs).
Both backends trace the identical body with identical PRNG folding, so
their outputs match bit for bit at equal (n, p, algorithm, seed).

Multi-axis meshes: a 2-D ``keys`` array of shape (d, n) is a batch of d
independent sort problems laid out over a (``data_axis``, ``axis``) mesh —
each row is sorted within its own p-sized sort-axis subgroup and the data
axis never communicates.  Because every collective resolves relative to
the named sort axis (see ``repro.core.comm.Collectives``), row r of the
batched output is bit-identical to a 1-D ``psort`` of row r at the same
(n, p, algorithm, seed).  On ``backend="shard_map"`` the mesh is a real
2-D device mesh (``repro.dist.sharding.sort_mesh``); on ``backend="sim"``
it is emulated via ``comm.sim_map(..., mesh=(d, p))``.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from functools import partial
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import comm, selection
from .types import (SortShard, key_to_uint, local_kernels, make_shard,
                    pad_value, uint_to_key)

BACKENDS = ("shard_map", "sim")

# algorithms with a slotted exchange the streamed pipeline can overlap; the
# rest (ppermute/all_gather structures) have nothing to stream and run the
# barrier path under overlap=True unchanged (trivially bitwise-equal)
_OVERLAP_ALGOS = ("rams", "ntb-ams", "ssort", "ns-ssort")


@dataclasses.dataclass(frozen=True)
class SortConfig:
    """Everything that shapes one distributed sort, in one hashable object.

    ``psort(keys, config=SortConfig(...))`` is the primary call style; the
    jit caches key on the whole config, so two calls with equal configs hit
    the same executable.  Fields group into:

    **Topology** — ``p`` (PE count; read off ``mesh``/``mesh_shape`` when
    omitted on shard_map), ``mesh`` (explicit device mesh, shard_map only;
    excluded from equality/hash — pass the same mesh object to reuse the
    cache), ``axis``/``data_axis`` (mesh axis names), ``mesh_shape`` +
    ``mesh_axes`` (hierarchical nested-axis runs), ``levels`` (AMS level
    count).

    **Execution** — ``backend`` (``"shard_map"`` | ``"sim"``),
    ``algorithm`` (``"auto"`` consults the cost model), ``cost_model``
    (:class:`repro.core.selection.CostModel` machine profile),
    ``capacity_factor`` (slack of the per-PE shard buffers).

    **Resilience / streaming** — ``fault_policy``
    (:class:`repro.runtime.failures.FaultPolicy`; mutable, excluded from
    equality/hash), ``external``
    (:class:`repro.core.external.ExternalPolicy` out-of-core streaming),
    and ``overlap`` (pipeline every slotted exchange against the local
    merge via ``comm.alltoall_stream`` — bitwise-identical output; a no-op
    for algorithms without a slotted all_to_all).

    ``algo_kw`` holds algorithm-specific keywords (``slot_factor``,
    ``oracle_splitters``, ``tie_break``, …) as a sorted tuple of pairs —
    :meth:`from_kwargs` splits a flat kwarg dict into fields and
    ``algo_kw``, which is also what the legacy-kwarg shim uses.

    See the README migration table for the legacy-kwarg ↔ field mapping.
    """

    # topology
    p: Optional[int] = None
    mesh: Optional[Mesh] = dataclasses.field(default=None, compare=False)
    axis: str = "sort"
    data_axis: str = "data"
    mesh_shape: Optional[tuple] = None
    mesh_axes: tuple = ("inter", "intra")
    levels: Optional[int] = None
    # execution
    backend: str = "shard_map"
    algorithm: str = "auto"
    cost_model: Optional[selection.CostModel] = None
    capacity_factor: float = 2.0
    # resilience / streaming
    fault_policy: Optional[object] = dataclasses.field(default=None,
                                                       compare=False)
    external: Optional[object] = None
    overlap: bool = False
    # algorithm-specific keywords, normalized to a sorted tuple of pairs
    algo_kw: tuple = ()

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; expected "
                             f"{BACKENDS}")
        if self.mesh_shape is not None:
            object.__setattr__(self, "mesh_shape",
                               tuple(int(v) for v in self.mesh_shape))
        object.__setattr__(self, "mesh_axes", tuple(self.mesh_axes))
        kw = dict(self.algo_kw) if not isinstance(self.algo_kw, dict) \
            else self.algo_kw
        norm = {k: tuple(v) if isinstance(v, list) else v
                for k, v in kw.items()}
        object.__setattr__(self, "algo_kw", tuple(sorted(norm.items())))

    @classmethod
    def from_kwargs(cls, **kw) -> "SortConfig":
        """Split a flat legacy-style kwarg dict into config fields plus
        ``algo_kw`` (anything that is not a field)."""
        cfg = {k: kw.pop(k) for k in list(kw) if k in _CONFIG_FIELDS}
        return cls(algo_kw=kw, **cfg)

    def replace(self, **changes) -> "SortConfig":
        return dataclasses.replace(self, **changes)


_CONFIG_FIELDS = frozenset(
    f.name for f in dataclasses.fields(SortConfig)) - {"algo_kw"}


def _coerce_config(config, legacy: dict, caller: str) -> SortConfig:
    """Resolve the (config | legacy kwargs) call styles to one SortConfig.

    Exactly one :class:`DeprecationWarning` per legacy-style call; mixing
    the styles is a :class:`TypeError`.  A bare int ``config`` is the old
    positional ``p``.
    """
    if isinstance(config, (int, np.integer)):      # legacy positional p
        legacy = {"p": int(config), **legacy}
        config = None
    if config is not None:
        if legacy:
            raise TypeError(
                f"{caller}() got both config= and legacy keyword arguments "
                f"{sorted(legacy)}; move them into the SortConfig")
        if not isinstance(config, SortConfig):
            raise TypeError(f"{caller}() config must be a SortConfig, got "
                            f"{type(config).__name__}")
        return config
    if not legacy:
        return SortConfig()
    warnings.warn(
        f"{caller}(keys, p=..., algorithm=..., ...) keyword style is "
        f"deprecated; pass {caller}(..., config=SortConfig(...)) instead "
        f"(field mapping: README 'Migrating to SortConfig')",
        DeprecationWarning, stacklevel=3)
    return SortConfig.from_kwargs(**legacy)


def default_mesh(p: Optional[int] = None, axis: str = "sort") -> Mesh:
    devs = jax.devices()
    p = p or len(devs)
    if p > len(devs):
        raise ValueError(f"requested p={p} > available devices {len(devs)}"
                         f" (use backend='sim' for emulated PE counts)")
    return Mesh(np.array(devs[:p]), (axis,))


def _algorithm_fn(name: str):
    # lazy per-name imports to avoid cycles and partial-build breakage
    if name in ("rquick", "ntb-quick"):
        from .rquick import rquick
        fn = rquick if name == "rquick" else partial(rquick, robust=False)
    elif name == "rfis":
        from .rfis import rfis as fn
    elif name in ("rams", "ntb-ams"):
        from .rams import rams
        fn = rams if name == "rams" else partial(rams, tie_break=False)
    elif name == "bitonic":
        from .bitonic import bitonic as fn
    elif name in ("ssort", "ns-ssort"):
        from .samplesort import samplesort
        fn = samplesort if name == "ssort" else partial(samplesort, robust=False)
    elif name == "gatherm":
        from .gatherm import gather_merge as fn
    elif name == "allgatherm":
        from .gatherm import allgather_merge_sort as fn
    else:
        raise ValueError(f"unknown algorithm {name!r}")
    return _wrap_result(fn)


def _wrap_result(fn):
    def wrapped(shard, axis_name, p, **kw):
        out = fn(shard, axis_name, p, **kw)
        if isinstance(out, tuple) and not hasattr(out, "shard"):
            return out
        return out.shard, out.overflow
    return wrapped


def _sort_body(axis_name, p, algorithm, capacity, out_capacity, algo_kw):
    """The per-PE SPMD body shared by both backends.

    Takes (keys (per,), count ()) for one PE, returns (keys (out_cap,),
    idx (out_cap,), count (), overflow ()).
    """
    algo_kw = dict(algo_kw)

    def body(keys_pe, count_pe):
        per = keys_pe.shape[0]
        # global index payload proves permutation-ness in tests
        base = comm.axis_index(axis_name).astype(jnp.uint32) * np.uint32(per)
        idx = base + jnp.arange(per, dtype=jnp.uint32)
        # unsorted: every algorithm sorts its own input (after its shuffle,
        # where it has one), so a sort here would be repeated work
        shard = make_shard(keys_pe, count=count_pe, capacity=capacity,
                           vals={"idx": idx}, sort_local=False)
        fn = _algorithm_fn(algorithm)
        out, overflow = fn(shard, axis_name, p, **algo_kw)
        overflow = overflow + jnp.maximum(out.count - out_capacity, 0)
        ok = jnp.minimum(out.count, out_capacity)
        keys = out.keys[:out_capacity]
        idx = out.vals.get("idx", jnp.zeros((out.capacity,), jnp.uint32))[:out_capacity]
        return keys, idx, ok, overflow

    return body


@partial(jax.jit, static_argnames=("cfg", "algorithm", "axis_name", "p",
                                   "capacity", "out_capacity", "mesh",
                                   "algo_kw", "pallas"))
def _psort_jit(keys2d, counts, mesh, cfg, axis_name, p, algorithm, capacity,
               out_capacity, algo_kw, pallas):
    body = _sort_body(axis_name, p, algorithm, capacity, out_capacity, algo_kw)

    def blk(keys_blk, count_blk):
        k, i, c, o = body(keys_blk[0], count_blk[0])
        return k[None], i[None], c[None], o[None]

    out = jax.shard_map(blk, mesh=mesh,
                        in_specs=(P(axis_name), P(axis_name)),
                        out_specs=(P(axis_name),) * 4,
                        check_vma=False)(keys2d, counts)
    return out


@partial(jax.jit, static_argnames=("cfg", "algorithm", "axis_name", "p",
                                   "capacity", "out_capacity", "algo_kw",
                                   "pallas"))
def _psort_sim_jit(keys2d, counts, cfg, axis_name, p, algorithm, capacity,
                   out_capacity, algo_kw, pallas):
    body = _sort_body(axis_name, p, algorithm, capacity, out_capacity, algo_kw)
    return comm.sim_map(body, axis_name, p)(keys2d, counts)


@partial(jax.jit, static_argnames=("cfg", "algorithm", "axis_name",
                                   "data_axis", "p", "capacity",
                                   "out_capacity", "mesh", "algo_kw",
                                   "pallas"))
def _psort2_jit(keys3d, counts, mesh, cfg, axis_name, data_axis, p, algorithm,
                capacity, out_capacity, algo_kw, pallas):
    """Batched psort over the sort axis of a 2-D (data, sort) device mesh."""
    body = _sort_body(axis_name, p, algorithm, capacity, out_capacity, algo_kw)

    def blk(keys_blk, count_blk):          # (1, 1, per), (1, 1)
        k, i, c, o = body(keys_blk[0, 0], count_blk[0, 0])
        return (k[None, None], i[None, None], c[None, None], o[None, None])

    out = jax.shard_map(blk, mesh=mesh,
                        in_specs=(P(data_axis, axis_name),
                                  P(data_axis, axis_name)),
                        out_specs=(P(data_axis, axis_name),) * 4,
                        check_vma=False)(keys3d, counts)
    return out


@partial(jax.jit, static_argnames=("cfg", "algorithm", "axis_name",
                                   "data_axis", "d", "p", "capacity",
                                   "out_capacity", "algo_kw", "pallas"))
def _psort2_sim_jit(keys3d, counts, cfg, axis_name, data_axis, d, p,
                    algorithm, capacity, out_capacity, algo_kw, pallas):
    body = _sort_body(axis_name, p, algorithm, capacity, out_capacity, algo_kw)
    return comm.sim_map(body, axis_name, p, mesh=(d, p),
                        data_axis=data_axis)(keys3d, counts)


@partial(jax.jit, static_argnames=("cfg", "algorithm", "axis_name",
                                   "data_axis", "axes", "p", "capacity",
                                   "out_capacity", "mesh", "algo_kw",
                                   "pallas"))
def _psort_nested_jit(keys_nd, counts, mesh, cfg, axis_name, data_axis, axes,
                      p, algorithm, capacity, out_capacity, algo_kw, pallas):
    """psort over the virtual flat axis of a nested (inter, intra) mesh.

    The body is the *same* per-PE body as the flat path; its collectives
    name ``axis_name`` and the :func:`repro.core.comm.nested` scope
    decomposes them onto the real mesh axes while tracing.  ``data_axis``
    (when not None) leads for batched keys.
    """
    body = _sort_body(axis_name, p, algorithm, capacity, out_capacity, algo_kw)
    names = ((data_axis,) if data_axis else ()) + tuple(n for n, _ in axes)
    nlead = len(names)

    def blk(keys_blk, count_blk):
        with comm.nested(axis_name, axes):
            k, i, c, o = body(keys_blk.reshape(keys_blk.shape[nlead:]),
                              count_blk.reshape(()))
        dims = tuple(range(nlead))
        return tuple(jnp.expand_dims(v, dims) for v in (k, i, c, o))

    out = jax.shard_map(blk, mesh=mesh,
                        in_specs=(P(*names), P(*names)),
                        out_specs=(P(*names),) * 4,
                        check_vma=False)(keys_nd, counts)
    return out


@partial(jax.jit, static_argnames=("cfg", "algorithm", "axis_name",
                                   "data_axis", "d", "axes", "p", "capacity",
                                   "out_capacity", "algo_kw", "pallas"))
def _psort_nested_sim_jit(keys_nd, counts, cfg, axis_name, data_axis, d, axes,
                          p, algorithm, capacity, out_capacity, algo_kw,
                          pallas):
    body = _sort_body(axis_name, p, algorithm, capacity, out_capacity, algo_kw)
    return comm.sim_map(body, axis_name, p, nested=axes,
                        mesh=(d, p) if data_axis else None,
                        data_axis=data_axis)(keys_nd, counts)


def psort(keys, config=None, *, return_info: bool = False, **legacy):
    """Sort a host array over the ``axis`` mesh axis with p (emulated) PEs.

    ``config`` is a :class:`SortConfig` carrying every knob — topology,
    execution, resilience/streaming and algorithm keywords.  The legacy
    flat-kwarg style (``psort(x, p=4, algorithm="rquick", ...)``) still
    works through a shim that builds the equivalent config and emits one
    :class:`DeprecationWarning` per call; a bare int second argument is
    the old positional ``p``.  Mixing ``config=`` with legacy kwargs is a
    :class:`TypeError`.  See the README's "Migrating to SortConfig" table.

    Returns the sorted array (and an info dict with overflow / balance when
    ``return_info``).  1-D ``keys`` of shape (n,) are one global sort
    problem; 2-D ``keys`` of shape (d, n) are d **independent** problems
    laid out over a (``data_axis``, ``axis``) mesh — each row is sorted
    within its own sort-axis subgroup, bit-identical to d separate 1-D
    calls (the multi-axis-mesh contract, see ``docs/ARCHITECTURE.md``).

    ``mesh`` (``backend="shard_map"`` only) supplies the device mesh: 1-D
    over ``axis`` for 1-D keys, 2-D over (``data_axis``, ``axis``) for 2-D
    keys (default: ``repro.dist.sharding.sort_mesh``).  ``backend="sim"``
    runs meshless and needs an explicit ``p``; the data-axis extent is
    read off ``keys.shape[0]``.

    **Hierarchical meshes** — ``mesh_shape=(p_outer, p_inner)`` sorts over
    the *nested* axis pair ``mesh_axes`` (default ``("inter", "intra")``)
    of a hierarchical mesh instead of one flat axis: the algorithms still
    see a single virtual axis of size ``p_outer·p_inner``, but every
    collective is decomposed onto the real axes
    (``repro.core.comm.NestedCollectives``), and RAMS aligns its level
    schedule to the axis boundary (``repro.core.rams.nested_level_bits``)
    so the first level's all_to_all is the **only** exchange crossing the
    slow outer axis — every later level recurses inside an intra subcube.
    Bitwise-identical to the flat run of the same schedule.  On
    ``backend="shard_map"`` the mesh is ``sort_mesh(shape=mesh_shape)``;
    on ``backend="sim"`` the hierarchy is emulated (``p`` may be omitted).

    ``levels`` (multi-level AMS family only) picks the number of RAMS
    levels: flat it forwards to ``rams(levels=...)``; nested, the first
    level is pinned to the outer axis and ``levels - 1`` levels split the
    inner axis.  ``levels=1`` is the single-exchange samplesort structure.

    ``cost_model`` parameterizes ``algorithm="auto"``: a
    :class:`repro.core.selection.CostModel` machine profile (e.g. loaded
    from a ``profiles/<machine>.json`` written by
    ``benchmarks/calibrate.py``); defaults to the prior profile.

    **Fault tolerance** — ``fault_policy`` (a
    :class:`repro.runtime.failures.FaultPolicy`, sim backend only) runs
    the sort under its :class:`repro.core.comm.FaultPlan`: each attempt
    is freshly traced under a :class:`repro.core.comm.FaultyCollectives`
    decorator, a fired kill (:class:`repro.core.comm.PEFailure`) or a
    watchdog-flagged straggler excludes the PE, the topology is re-planned
    (``repro.runtime.elastic.plan_sort_rescale`` — survivors rounded down
    to a power of two, nested inner axis preserved while it fits), the
    input is redistributed over the new mesh and the sort re-runs —
    ``algorithm="auto"`` re-consults ``select_algorithm`` at the reduced
    p.  Retries are bounded by ``policy.max_restarts`` via
    ``repro.runtime.failures.run_with_restarts``.  Afterwards
    ``policy.trace`` holds the merged :class:`repro.core.comm.CommTrace`
    (injected ``fault:*`` events, ``rescale`` markers, regular launches)
    and ``policy.attempts`` one record per attempt; with ``return_info``
    the info dict gains ``"fault"`` and ``"comm_trace"`` entries.  See
    ``docs/ARCHITECTURE.md`` ("Fault tolerance").

    **External memory** — ``external`` (a
    :class:`repro.core.external.ExternalPolicy`, sim backend, 1-D flat
    axis only) lifts the device-memory cap on n/p: shards larger than
    ``external.budget`` elements live in host memory and stream through
    the device in run-formation / splitter-fit / per-run-exchange /
    k-way-merge passes (see ``repro/core/external.py``).  The output is
    bitwise-equal to the in-core path — it is *the* globally sorted
    array.  ``algorithm="auto"`` consults the cost model's external
    regime (``select_algorithm(..., budget=...)``); shards that fit the
    budget run the normal in-core path.  The ``REPRO_EXTERNAL_BUDGET``
    environment variable applies a default policy when ``external`` is
    omitted.  Composes with ``fault_policy``: a kill during any external
    pass excludes the PE and re-runs the whole multi-pass pipeline at the
    reduced topology.

    >>> import numpy as np
    >>> from repro.core.api import SortConfig, psort
    >>> x = np.array([5, 3, 1, 4, 2, 9, 8, 6], np.int32)
    >>> cfg = SortConfig(p=4, algorithm="rquick", backend="sim")
    >>> np.asarray(psort(x, config=cfg))
    array([1, 2, 3, 4, 5, 6, 8, 9], dtype=int32)

    A batch of rows sorts within per-row subgroups of a (d, p) mesh — the
    rows never exchange elements:

    >>> xs = np.stack([x, x[::-1] * 10])
    >>> np.asarray(psort(xs, config=cfg))
    array([[ 1,  2,  3,  4,  5,  6,  8,  9],
           [10, 20, 30, 40, 50, 60, 80, 90]], dtype=int32)

    A hierarchical (2 × 2) mesh — same result, collectives split across
    the inter/intra axes:

    >>> np.asarray(psort(x, config=SortConfig(mesh_shape=(2, 2),
    ...                                       algorithm="rams",
    ...                                       backend="sim")))
    array([1, 2, 3, 4, 5, 6, 8, 9], dtype=int32)

    A sort that loses PE 3 restarts at the reduced power-of-two topology
    (4 PEs lose one → 3 survivors → p = 2) and still returns the exact
    sorted multiset:

    >>> from repro.core.comm import FaultPlan, kill_pe
    >>> from repro.runtime.failures import FaultPolicy
    >>> pol = FaultPolicy(plan=FaultPlan((kill_pe(3),)))
    >>> np.asarray(psort(x, config=cfg.replace(fault_policy=pol)))
    array([1, 2, 3, 4, 5, 6, 8, 9], dtype=int32)
    >>> [a["p"] for a in pol.attempts]
    [4, 2]
    >>> [e.primitive for e in pol.trace.injected()]
    ['fault:kill', 'rescale']

    A shard budget of 4 elements streams the 16-element-per-PE problem
    through the device in 4 runs per PE — same sorted output:

    >>> from repro.core.external import ExternalPolicy
    >>> big = np.arange(64, dtype=np.int32)[::-1].copy()
    >>> out = psort(big, config=SortConfig(
    ...     p=4, backend="sim", external=ExternalPolicy(budget=4)))
    >>> np.array_equal(np.asarray(out), np.sort(big))
    True

    **Profiler spans** — each call writes ``jax.profiler.TraceAnnotation``
    spans on the calling thread, on the profiler's clock: ``psort`` around
    the whole call and, on the in-core paths, its children in this order:
    ``psort.prepare`` (config, upload, ``key_to_uint``, padding, algorithm
    selection and the dispatch of the device program), ``psort.wait``
    (``block_until_ready`` on its outputs), ``psort.pull`` (device → host;
    ``bytes=`` the bytes pulled) and ``psort.assemble`` (the per-PE joins,
    the answer's upload and ``uint_to_key``; ``bytes=`` the answer's
    bytes).  The fault-policy and external paths run after
    ``psort.prepare`` under ``psort`` alone.  The device program carries
    ``jax.named_scope`` phase names (each ``comm.tagged`` tag,
    ``local_sort``, ``merge_shards``, ``partition_buckets``,
    ``alltoall_route``) in its ops' ``op_name`` metadata.
    """
    with jax.profiler.TraceAnnotation("psort"):
        with jax.profiler.TraceAnnotation("psort.prepare"):
            cfg = _coerce_config(config, legacy, caller="psort")
            launched = _launch(keys, cfg, return_info)
        if not isinstance(launched, _Launched):
            return launched()              # the fault-policy or external path
        return _collect(launched, return_info)


class _Launched(NamedTuple):
    """An in-core sort whose device program is dispatched: its padded
    per-PE outputs, shaped ``(d, p, ...)``, and what assembling them needs."""
    keys: jax.Array
    idx: jax.Array
    counts: jax.Array
    overflow: jax.Array
    n: int
    batched: bool
    algorithm: str
    orig_dtype: np.dtype
    cfg: SortConfig


def _launch(keys, cfg: SortConfig, return_info: bool):
    """``psort`` up to the return of the jitted device program.

    Returns a :class:`_Launched`, or, for the fault-policy and external
    paths, a callable that runs the rest of the sort and returns ``psort``'s
    result."""
    p, algorithm, mesh = cfg.p, cfg.algorithm, cfg.mesh
    axis, data_axis = cfg.axis, cfg.data_axis
    mesh_shape, mesh_axes, levels = cfg.mesh_shape, cfg.mesh_axes, cfg.levels
    capacity_factor, backend = cfg.capacity_factor, cfg.backend
    cost_model, fault_policy = cfg.cost_model, cfg.fault_policy
    external = cfg.external
    algo_kw = dict(cfg.algo_kw)
    if levels is not None and algorithm not in ("auto", "rams", "ntb-ams"):
        raise ValueError(f"levels= applies to the multi-level AMS family "
                         f"(or 'auto'), not algorithm={algorithm!r}")
    keys = jnp.asarray(keys)
    if keys.ndim not in (1, 2):
        raise ValueError(f"keys must be 1-D (one sort) or 2-D (a batch of "
                         f"independent sorts); got shape {keys.shape}")
    batched = keys.ndim == 2
    d = keys.shape[0] if batched else 1
    if mesh_shape is not None:
        p_o, p_i = (int(v) for v in mesh_shape)
        if (p_o & (p_o - 1)) or (p_i & (p_i - 1)) or p_o < 1 or p_i < 1:
            raise ValueError(f"mesh_shape={mesh_shape} entries must be "
                             f"powers of two (hypercube layout)")
        if p is not None and p != p_o * p_i:
            raise ValueError(f"p={p} inconsistent with mesh_shape="
                             f"{tuple(mesh_shape)}")
        p = p_o * p_i
        if backend == "shard_map":
            if mesh is None:
                from repro.dist.sharding import sort_mesh
                mesh = sort_mesh(shape=(p_o, p_i), d=d if batched else 1,
                                 data_axis=data_axis, mesh_axes=mesh_axes)
            want = dict(zip(mesh_axes, (p_o, p_i)))
            if batched:
                want[data_axis] = d
            for a, sz in want.items():
                if mesh.shape.get(a) != sz:
                    raise ValueError(f"mesh axis {a!r} must have size {sz}; "
                                     f"mesh has {dict(mesh.shape)}")
        elif mesh is not None:
            raise ValueError("backend='sim' runs meshless; drop the mesh arg")
    elif backend == "shard_map":
        if batched:
            if mesh is None:
                from repro.dist.sharding import sort_mesh
                mesh = sort_mesh(p, d=d, axis=axis, data_axis=data_axis)
            for a in (data_axis, axis):
                if a not in mesh.shape:
                    raise ValueError(f"2-D keys need a mesh with axes "
                                     f"({data_axis!r}, {axis!r}); mesh has "
                                     f"{tuple(mesh.shape)}")
            if mesh.shape[data_axis] != d:
                raise ValueError(f"keys.shape[0]={d} != mesh.shape"
                                 f"[{data_axis!r}]={mesh.shape[data_axis]}")
        else:
            mesh = mesh or default_mesh(p, axis)
        p = mesh.shape[axis]
    else:
        if mesh is not None:
            raise ValueError("backend='sim' runs meshless; drop the mesh arg")
        if p is None:
            raise ValueError("backend='sim' needs an explicit p")
    if p & (p - 1):
        raise ValueError(f"p={p} must be a power of two (hypercube layout)")
    n = keys.shape[-1]
    orig_dtype = keys.dtype
    u = key_to_uint(keys)

    external = _resolve_external(external, backend)
    if external is not None:
        if backend != "sim":
            raise ValueError("external= requires backend='sim' (host-"
                             "streamed shards run on emulated PEs)")
        if batched:
            raise ValueError("external= supports 1-D keys only (each run "
                             "pass is one global sort problem)")
        if mesh_shape is not None:
            raise ValueError("external= runs on one flat axis; drop "
                             "mesh_shape")
    elif algorithm == "external":
        raise ValueError("algorithm='external' needs external="
                         "ExternalPolicy(...) (or REPRO_EXTERNAL_BUDGET)")

    if fault_policy is not None:
        if backend != "sim":
            raise ValueError("fault_policy= requires backend='sim' (the "
                             "fault-injection lane runs on emulated PEs)")
        return partial(
            _psort_faulty,
            u, n, d, batched, orig_dtype, p=p, algorithm=algorithm,
            policy=fault_policy, axis=axis, data_axis=data_axis,
            mesh_shape=(p_o, p_i) if mesh_shape is not None else None,
            mesh_axes=mesh_axes, levels=levels,
            capacity_factor=capacity_factor, return_info=return_info,
            cost_model=cost_model, algo_kw=algo_kw, external=external,
            overlap=cfg.overlap)

    per = -(-max(n, 1) // p)                       # ceil(n/p)
    capacity = max(4, int(np.ceil(per * capacity_factor)))
    if algorithm == "auto":
        algorithm = selection.select_algorithm(
            n, p, model=cost_model, levels=levels, mesh_shape=mesh_shape,
            budget=external.budget if external is not None else None)
    if external is not None and (algorithm == "external"
                                 or per > external.budget):
        return partial(_psort_external, u, n, orig_dtype, p=p, axis=axis,
                       policy=external, return_info=return_info,
                       overlap=cfg.overlap)
    if cfg.overlap and algorithm in _OVERLAP_ALGOS:
        algo_kw.setdefault("overlap", True)
    if algorithm in ("rams", "ntb-ams"):
        if mesh_shape is not None:
            from .rams import nested_level_bits
            algo_kw.setdefault(
                "level_bits", tuple(nested_level_bits(p_o, p_i, levels)))
        elif levels is not None:
            algo_kw.setdefault("levels", levels)
    out_capacity = _out_capacity(algorithm, n, p, per, capacity)

    pad = pad_value(u.dtype)
    row_counts = jnp.minimum(jnp.maximum(n - per * jnp.arange(p), 0),
                             per).astype(jnp.int32)
    kw = tuple(sorted(algo_kw.items()))
    # jit caches key on the local-kernel policy: the policy is read at
    # trace time, so without this a cached executable would silently
    # ignore a toggle between calls of the same signature.
    pl = local_kernels()
    if mesh_shape is not None:
        axes = ((mesh_axes[0], p_o), (mesh_axes[1], p_i))
        lead = (d,) if batched else ()
        flat = jnp.full(lead + (p * per,), pad, u.dtype)
        flat = flat.at[..., :n].set(u)
        keys_nd = flat.reshape(lead + (p_o, p_i, per))
        counts_nd = jnp.broadcast_to(row_counts.reshape(p_o, p_i),
                                     lead + (p_o, p_i))
        da = data_axis if batched else None
        if backend == "shard_map":
            keys_out, idx_out, counts_out, overflow = _psort_nested_jit(
                keys_nd, counts_nd, mesh, cfg, axis, da, axes, p, algorithm,
                capacity, out_capacity, kw, pallas=pl)
        else:
            keys_out, idx_out, counts_out, overflow = _psort_nested_sim_jit(
                keys_nd, counts_nd, cfg, axis, da, d, axes, p, algorithm,
                capacity, out_capacity, kw, pallas=pl)
        keys_out = keys_out.reshape((d, p) + keys_out.shape[-1:])
        idx_out = idx_out.reshape((d, p) + idx_out.shape[-1:])
        counts_out = counts_out.reshape(d, p)
        overflow = overflow.reshape(d, p)
    elif batched:
        flat = jnp.full((d, p * per), pad, u.dtype).at[:, :n].set(u)
        keys3d = flat.reshape(d, p, per)
        counts = jnp.broadcast_to(row_counts, (d, p))
        if backend == "shard_map":
            keys_out, idx_out, counts_out, overflow = _psort2_jit(
                keys3d, counts, mesh, cfg, axis, data_axis, p, algorithm,
                capacity, out_capacity, kw, pallas=pl)
        else:
            keys_out, idx_out, counts_out, overflow = _psort2_sim_jit(
                keys3d, counts, cfg, axis, data_axis, d, p, algorithm,
                capacity, out_capacity, kw, pallas=pl)
    else:
        flat = jnp.full((p * per,), pad, u.dtype).at[:n].set(u)
        keys2d = flat.reshape(p, per)
        if backend == "shard_map":
            keys_out, idx_out, counts_out, overflow = _psort_jit(
                keys2d, row_counts, mesh, cfg, axis, p, algorithm, capacity,
                out_capacity, kw, pallas=pl)
        else:
            keys_out, idx_out, counts_out, overflow = _psort_sim_jit(
                keys2d, row_counts, cfg, axis, p, algorithm, capacity,
                out_capacity, kw, pallas=pl)
        keys_out, idx_out = keys_out[None], idx_out[None]
        counts_out, overflow = counts_out[None], overflow[None]

    return _Launched(keys_out, idx_out, counts_out, overflow, n, batched,
                     algorithm, orig_dtype, cfg)


def _collect(job: _Launched, return_info: bool):
    """The in-core layouts' shared tail: wait for the device program, pull
    its padded per-PE outputs and assemble the answer from them."""
    outs = (job.keys, job.counts) + ((job.idx, job.overflow)
                                     if return_info else ())
    with jax.profiler.TraceAnnotation("psort.wait"):
        jax.block_until_ready(outs)
    with jax.profiler.TraceAnnotation("psort.pull",
                                      bytes=sum(o.nbytes for o in outs)):
        outs = [np.asarray(o) for o in outs]
    keys_out, counts_out = outs[:2]                # (d, p, out_capacity), (d, p)
    n, (d, p) = job.n, counts_out.shape
    pe_range = range(1) if job.algorithm == "allgatherm" else range(p)
    answer_bytes = int(counts_out[:, pe_range].sum()) \
        * np.dtype(job.orig_dtype).itemsize
    with jax.profiler.TraceAnnotation("psort.assemble", bytes=answer_bytes):
        rows = [np.concatenate([keys_out[r, i, :counts_out[r, i]]
                                for i in pe_range]) for r in range(d)]
        result = uint_to_key(
            jnp.asarray(np.stack(rows) if job.batched else rows[0]),
            job.orig_dtype)
        if not return_info:
            return result
        idx_out, overflow = outs[2:]
        perms = [np.concatenate([idx_out[r, i, :counts_out[r, i]]
                                 for i in range(p)]) if n
                 else np.zeros((0,), np.uint32) for r in range(d)]
        info = {
            "algorithm": job.algorithm,
            "backend": job.cfg.backend,
            "mesh_shape": job.cfg.mesh_shape,
            "counts": counts_out if job.batched else counts_out[0],
            "overflow": int(overflow.sum()),
            "balance": counts_out.max() / max(1.0, n / p),
            "perm": np.stack(perms) if job.batched else perms[0],
            "n": n,
            "d": d,
        }
        return result, info


def _out_capacity(algorithm: str, n: int, p: int, per: int, capacity: int) -> int:
    if algorithm in ("gatherm", "allgatherm"):
        return max(1, p * per)                     # concentrated output
    return capacity


def _resolve_external(external, backend: str):
    """Explicit policy wins; else ``REPRO_EXTERNAL_BUDGET`` (sim only)."""
    if external is not None:
        return external
    env = os.environ.get("REPRO_EXTERNAL_BUDGET")
    if env and backend == "sim":
        from .external import ExternalPolicy
        return ExternalPolicy(budget=int(env))
    return None


def _psort_external(u, n, orig_dtype, *, p, axis, policy, return_info,
                    overlap=False):
    """The non-fault ``psort(..., external=...)`` tail: run the four
    external passes once and reassemble the host output exactly like the
    in-core paths.  Ambient collectives decorators (``comm.counting()``)
    apply — the passes resolve ``impl`` per ``sim_map`` call."""
    from .external import _psort_external_once
    keys_out, idx_out, counts_out, overflow = _psort_external_once(
        u, n, axis=axis, p=p, policy=policy, impl=None, overlap=overlap)
    rows = np.concatenate([keys_out[0, pe, :counts_out[0, pe]]
                           for pe in range(p)])
    result = uint_to_key(jnp.asarray(rows), orig_dtype)
    if return_info:
        per = -(-max(n, 1) // p)
        perm = (np.concatenate([idx_out[0, pe, :counts_out[0, pe]]
                                for pe in range(p)]) if n
                else np.zeros((0,), np.uint32))
        info = {
            "algorithm": "external",
            "backend": "sim",
            "mesh_shape": None,
            "counts": counts_out[0],
            "overflow": int(np.asarray(overflow).sum()),
            "balance": counts_out.max() / max(1.0, n / p),
            "perm": perm,
            "n": n,
            "d": 1,
            "external": {
                "budget": policy.budget,
                "runs": max(1, -(-per // policy.budget)),
                "merge": policy.merge,
            },
        }
        return result, info
    return result


def _psort_sim_once(u, n, d, batched, *, axis, data_axis, p, mesh_shape,
                    mesh_axes, algorithm, capacity_factor, levels, algo_kw,
                    impl):
    """One sim-backend sort attempt at a fixed topology under ``impl``.

    The fault lane's executor: pads/redistributes the full key array over
    the *current* p, builds the per-PE body, and runs it under a **fresh**
    ``jax.jit`` — injection and counting act at trace time, so the cached
    module-level jits (which would replay nothing on a cache hit) cannot
    be used here.  Returns host arrays ``(keys, idx, counts, overflow)``
    of shapes ``(d, p, out_cap) ×2, (d, p) ×2``.
    """
    per = -(-max(n, 1) // p)
    capacity = max(4, int(np.ceil(per * capacity_factor)))
    kw = dict(algo_kw)
    if algorithm in ("rams", "ntb-ams"):
        if mesh_shape is not None:
            from .rams import nested_level_bits
            kw.setdefault("level_bits", tuple(nested_level_bits(
                mesh_shape[0], mesh_shape[1], levels)))
        elif levels is not None:
            kw.setdefault("levels", levels)
    out_capacity = _out_capacity(algorithm, n, p, per, capacity)
    body = _sort_body(axis, p, algorithm, capacity, out_capacity,
                      tuple(sorted(kw.items())))
    pad = pad_value(u.dtype)
    row_counts = jnp.minimum(jnp.maximum(n - per * jnp.arange(p), 0),
                             per).astype(jnp.int32)
    lead = (d,) if batched else ()
    flat = jnp.full(lead + (p * per,), pad, u.dtype)
    flat = flat.at[..., :n].set(u)
    da = data_axis if batched else None
    if mesh_shape is not None:
        p_o, p_i = mesh_shape
        axes = ((mesh_axes[0], p_o), (mesh_axes[1], p_i))
        keys_nd = flat.reshape(lead + (p_o, p_i, per))
        counts_nd = jnp.broadcast_to(row_counts.reshape(p_o, p_i),
                                     lead + (p_o, p_i))
        runner = comm.sim_map(body, axis, p, impl=impl, nested=axes,
                              mesh=(d, p) if batched else None, data_axis=da)
        k, i, c, o = jax.jit(runner)(keys_nd, counts_nd)
        k = k.reshape((d, p) + k.shape[-1:])
        i = i.reshape((d, p) + i.shape[-1:])
        c, o = c.reshape(d, p), o.reshape(d, p)
    elif batched:
        runner = comm.sim_map(body, axis, p, impl=impl, mesh=(d, p),
                              data_axis=da)
        k, i, c, o = jax.jit(runner)(flat.reshape(d, p, per),
                                     jnp.broadcast_to(row_counts, (d, p)))
    else:
        runner = comm.sim_map(body, axis, p, impl=impl)
        k, i, c, o = jax.jit(runner)(flat.reshape(p, per), row_counts)
        k, i, c, o = k[None], i[None], c[None], o[None]
    return np.asarray(k), np.asarray(i), np.asarray(c), np.asarray(o)


def _psort_faulty(u, n, d, batched, orig_dtype, *, p, algorithm, policy,
                  axis, data_axis, mesh_shape, mesh_axes, levels,
                  capacity_factor, return_info, cost_model, algo_kw,
                  external=None, overlap=False):
    """The ``psort(..., fault_policy=...)`` driver (sim backend).

    Attempt loop (bounded by ``repro.runtime.failures.run_with_restarts``):
    trace the sort afresh under ``FaultyCollectives`` executing the
    policy's surviving :class:`repro.core.comm.FaultPlan`; on a
    :class:`repro.core.comm.PEFailure` — raised by a fired kill, or by
    this driver for a watchdog-flagged straggler — exclude the PE, plan
    the reduced topology (``elastic.plan_sort_rescale``), record a
    ``rescale`` trace event carrying the new extent, and retry.  Progress
    = shrinking p, so a rescale that fails to shrink trips the loop's
    no-progress give-up rather than burning the restart budget.
    """
    from repro.runtime.elastic import plan_sort_rescale
    from repro.runtime.failures import flag_stragglers, run_with_restarts

    trace = policy.trace if policy.trace is not None else comm.CommTrace()
    policy.trace = trace
    log = policy.logger if policy.logger is not None else (lambda *a: None)
    plan0 = policy.plan if policy.plan is not None else comm.FaultPlan()
    if not isinstance(plan0, comm.FaultPlan):
        plan0 = comm.FaultPlan(tuple(plan0))
    state = {"p": p, "mesh_shape": mesh_shape, "plan": plan0,
             "failed": ()}
    policy.attempts.clear()

    def attempt(_start):
        p_cur, ms = state["p"], state["mesh_shape"]
        per_cur = -(-max(n, 1) // p_cur)
        algo = algorithm
        if algo == "auto":
            algo = selection.select_algorithm(
                n, p_cur, model=cost_model, levels=levels, mesh_shape=ms,
                budget=external.budget if external is not None else None)
        # external engages whenever the per-PE shard outgrows the budget —
        # a rescale shrinks p, so an attempt that started in-core can go
        # external after exclusion (and never the other way around)
        ext = external is not None and (algo == "external"
                                        or per_cur > external.budget)
        if ext:
            algo = "external"
        rec = {"p": p_cur, "mesh_shape": ms, "algorithm": algo, "ok": False}
        policy.attempts.append(rec)
        # faulty outside counting: a killed launch records its fault:kill
        # event but not the launch the dead PE never completed
        fc = comm.FaultyCollectives(
            comm.CountingCollectives(comm.SIM, trace), state["plan"], trace)
        if ext:
            from .external import _psort_external_once
            out = _psort_external_once(u, n, axis=axis, p=p_cur,
                                       policy=external, impl=fc,
                                       overlap=overlap)
        else:
            # overlap applies per attempt: the re-selected algorithm at the
            # reduced p may or may not have a streamable exchange
            kw_att = dict(algo_kw)
            if overlap and algo in _OVERLAP_ALGOS:
                kw_att.setdefault("overlap", True)
            out = _psort_sim_once(
                u, n, d, batched, axis=axis, data_axis=data_axis, p=p_cur,
                mesh_shape=ms, mesh_axes=mesh_axes, algorithm=algo,
                capacity_factor=capacity_factor, levels=levels,
                algo_kw=kw_att, impl=fc)
        times = [policy.base_step_time * fc.fired_delays.get(pe, 1.0)
                 for pe in range(p_cur)]
        slow = flag_stragglers(times, k_mad=policy.k_mad,
                               warmup=policy.warmup)
        if slow:
            raise comm.PEFailure(slow[0], phase="straggler")
        rec["ok"] = True
        return out + (p_cur, ms, algo)

    def rescale(e, restarts):
        p_cur, ms = state["p"], state["mesh_shape"]
        rplan = plan_sort_rescale(p_cur, (e.pe,), mesh_shape=ms)
        trace.add("rescale", 0, rplan.p_new, axis=axis, tag=e.phase,
                  pe=e.pe)
        why = "straggling" if e.phase == "straggler" else "failed"
        log(f"[psort] PE {e.pe} {why} at p={p_cur}; "
            f"rescaling to p={rplan.p_new}")
        state["p"] = rplan.p_new
        state["mesh_shape"] = rplan.mesh_shape
        state["plan"] = state["plan"].surviving(e.pe, rplan.p_new)
        state["failed"] += (e.pe,)

    keys_out, idx_out, counts_out, overflow, p_fin, ms_fin, algo_fin = \
        run_with_restarts(attempt, max_restarts=policy.max_restarts,
                          retry_on=(comm.PEFailure,), on_failure=rescale,
                          progress_fn=lambda: -state["p"], logger=log)

    pe_range = range(1) if algo_fin == "allgatherm" else range(p_fin)
    rows = [np.concatenate([keys_out[r, i, :counts_out[r, i]]
                            for i in pe_range]) for r in range(d)]
    result = uint_to_key(jnp.asarray(np.stack(rows) if batched else rows[0]),
                         orig_dtype)
    if return_info:
        perms = [np.concatenate([idx_out[r, i, :counts_out[r, i]]
                                 for i in range(p_fin)]) if n
                 else np.zeros((0,), np.uint32) for r in range(d)]
        info = {
            "algorithm": algo_fin,
            "backend": "sim",
            "mesh_shape": ms_fin,
            "counts": counts_out if batched else counts_out[0],
            "overflow": int(np.asarray(overflow).sum()),
            "balance": counts_out.max() / max(1.0, n / p_fin),
            "perm": np.stack(perms) if batched else perms[0],
            "n": n,
            "d": d,
            "fault": {
                "p_final": p_fin,
                "failed": state["failed"],
                "restarts": len(policy.attempts) - 1,
                "attempts": list(policy.attempts),
            },
            "comm_trace": trace,
        }
        return result, info
    return result


def trace_collectives(n: int, config=None, *args, d: int = 1,
                      **legacy) -> comm.CommTrace:
    """Count the collectives one ``psort`` call would launch, per PE.

    Takes the same :class:`SortConfig` as :func:`psort` (``d`` stays a
    direct keyword — it sizes the trace mesh, not the sort).  The legacy
    ``trace_collectives(n, p, algorithm, capacity_factor, ...)`` style
    still works through the deprecation shim.

    Abstractly evaluates the sim-backend body (shapes only, no FLOPs, no
    compile) under a :class:`repro.core.comm.CountingCollectives` decorator
    and returns the structured :class:`repro.core.comm.CommTrace`: launch
    counts, payload bytes and group sizes per primitive — the measured
    counterpart of the paper's Table I, and the feature vector
    ``benchmarks/calibrate.py`` fits the :class:`CostModel` against.

    ``d > 1`` traces the batched body over a (d, p) sim mesh instead.
    Collectives resolve relative to the sort axis, so the per-PE trace is
    independent of the data-axis extent — the subgroup-isolation property
    EXPERIMENTS.md's "Subgroup sort" grid is generated from.

    ``mesh_shape=(p_outer, p_inner)`` traces the **hierarchical** path:
    the counter sits inside the nested view, so every recorded event
    carries the real axis it targeted (``mesh_axes``) and the RAMS phase
    tag — ``trace.by_axis()`` splits inter- from intra-axis volume,
    ``trace.by_tag()`` attributes it per level.  ``levels`` forwards to
    the AMS level schedule exactly as in :func:`psort`.

    >>> from repro.core.api import SortConfig, trace_collectives
    >>> bt = SortConfig(p=8, algorithm="bitonic")
    >>> t1 = trace_collectives(64, bt)
    >>> t1.counts()["ppermute"] >= 6            # d·(d+1)/2 exchange rounds
    True
    >>> t2 = trace_collectives(64, bt, d=4)
    >>> t2.summary() == t1.summary()            # per-PE trace: no d term
    True

    On a nested mesh, RAMS crosses the slow outer axis with exactly one
    level's all_to_all (plus the initial shuffle) — every other level is
    intra-only:

    >>> t = trace_collectives(64 * 32, SortConfig(mesh_shape=(4, 16),
    ...                                           algorithm="rams"))
    >>> t.filter(primitive="all_to_all", axis="inter").tags()
    ['level0', 'shuffle']
    >>> [tag for tag, s in sorted(t.by_tag().items())
    ...  if "all_to_all" in s["counts"]]
    ['level0', 'level1', 'shuffle']

    ``external=ExternalPolicy(...)`` traces the out-of-core lane instead.
    Unlike the in-core trace this *executes* (splitter values steer the
    pass structure, so shapes alone don't determine the trace) on a
    deterministic seeded input — the trace is reproducible and additionally
    carries the injected ``ext:h2d``/``ext:d2h`` I/O pseudo-events
    (:meth:`repro.core.comm.CommTrace.io_bytes`) with per-pass tags:

    >>> from repro.core.external import ExternalPolicy
    >>> t = trace_collectives(256, SortConfig(
    ...     p=4, external=ExternalPolicy(budget=16)))
    >>> sorted(tag for tag in t.tags() if tag.startswith("ext:pass"))
    ['ext:pass0', 'ext:pass1', 'ext:pass2', 'ext:pass3']
    >>> t.io_bytes() > 0 and t.io_bytes() == t.filter(tag="ext:runs"
    ...     ).io_bytes() + t.filter(tag="ext:merge").io_bytes()
    True
    """
    if args:
        names = ("algorithm", "capacity_factor")
        if len(args) > len(names):
            raise TypeError(f"trace_collectives() takes at most "
                            f"{len(names)} legacy positional arguments "
                            f"after n/p ({names}); got {len(args)}")
        legacy.update(zip(names, args))
    cfg = _coerce_config(config, legacy, caller="trace_collectives")
    p, algorithm = cfg.p, cfg.algorithm
    capacity_factor, levels = cfg.capacity_factor, cfg.levels
    mesh_shape, mesh_axes = cfg.mesh_shape, cfg.mesh_axes
    external = cfg.external
    algo_kw = dict(cfg.algo_kw)
    if external is not None:
        if d > 1 or mesh_shape is not None:
            raise ValueError("external tracing covers the 1-D flat axis "
                             "only (the external lane's contract)")
        if p is None or p & (p - 1):
            raise ValueError(f"p={p} must be a power of two")
        from .external import _psort_external_once
        rng = np.random.default_rng(0xE87)
        u = jnp.asarray(rng.integers(0, 2 ** 32, size=max(n, 1),
                                     dtype=np.int64).astype(np.uint32))
        counter = comm.CountingCollectives(comm.SIM)
        _psort_external_once(u, n, axis="sort", p=p, policy=external,
                             impl=counter, overlap=cfg.overlap)
        return counter.trace
    axes = None
    if mesh_shape is not None:
        p_o, p_i = (int(v) for v in mesh_shape)
        if p is not None and p != p_o * p_i:
            raise ValueError(f"p={p} inconsistent with mesh_shape="
                             f"{tuple(mesh_shape)}")
        p = p_o * p_i
        axes = ((mesh_axes[0], p_o), (mesh_axes[1], p_i))
    if p is None:
        raise ValueError("trace_collectives needs p or mesh_shape")
    if p & (p - 1):
        raise ValueError(f"p={p} must be a power of two (hypercube layout)")
    if algorithm == "auto":
        algorithm = selection.select_algorithm(n, p, model=cfg.cost_model,
                                               levels=levels,
                                               mesh_shape=mesh_shape)
    if cfg.overlap and algorithm in _OVERLAP_ALGOS:
        algo_kw.setdefault("overlap", True)
    if algorithm in ("rams", "ntb-ams"):
        if mesh_shape is not None:
            from .rams import nested_level_bits
            algo_kw.setdefault(
                "level_bits", tuple(nested_level_bits(p_o, p_i, levels)))
        elif levels is not None:
            algo_kw.setdefault("levels", levels)
    per = -(-max(n, 1) // p)
    capacity = max(4, int(np.ceil(per * capacity_factor)))
    out_capacity = _out_capacity(algorithm, n, p, per, capacity)
    body = _sort_body("sort", p, algorithm, capacity, out_capacity,
                      tuple(sorted(algo_kw.items())))
    counter = comm.CountingCollectives(comm.SIM)
    mesh = (d, p) if d > 1 else None
    runner = comm.sim_map(body, "sort", p, impl=counter, mesh=mesh,
                          data_axis="data" if d > 1 else None, nested=axes)
    axis_lead = (p_o, p_i) if axes is not None else (p,)
    lead = ((d,) + axis_lead) if d > 1 else axis_lead
    jax.eval_shape(runner,
                   jax.ShapeDtypeStruct(lead + (per,), jnp.uint32),
                   jax.ShapeDtypeStruct(lead, jnp.int32))
    return counter.trace
