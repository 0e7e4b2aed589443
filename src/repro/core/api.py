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

import contextlib
import dataclasses
import os
import warnings
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import comm, selection
from .types import (key_to_uint, local_kernels, make_shard, pad_value,
                    uint_to_key)

BACKENDS = ("shard_map", "sim")

# algorithms with a slotted exchange the streamed pipeline can overlap; the
# rest (ppermute/all_gather structures) have nothing to stream and run the
# barrier path under overlap=True unchanged (trivially bitwise-equal)
_OVERLAP_ALGOS = ("rams", "ntb-ams", "ssort", "ns-ssort")


@dataclasses.dataclass(frozen=True)
class SortConfig:
    """Everything that shapes one distributed sort, in one hashable object.

    ``psort(keys, config=SortConfig(...))`` is the primary call style; the
    device program's jit cache keys on the plan settled from it, so two
    calls with equal configs hit the same executable.  Fields group into:

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


@dataclasses.dataclass(frozen=True)
class _Plan:
    """What one sort runs, settled once by :func:`_plan`.

    ``lead`` holds the leading dimensions of the per-PE arrays — ``(p,)``,
    ``(d, p)``, ``(p_o, p_i)`` or ``(d, p_o, p_i)`` — and ``names`` the mesh
    axes in ``lead``'s order; ``axes`` is the nested ``((outer, p_o),
    (inner, p_i))`` pair or None.  ``algorithm`` is ``"external"`` when the
    shards stream through the out-of-core lane.  The plan is the device
    program's jit key: ``n`` is left out of it, since the program reads only
    ``per``.
    """
    n: int = dataclasses.field(compare=False)
    d: int
    batched: bool
    p: int
    per: int
    lead: tuple
    names: tuple
    axes: Optional[tuple]
    axis: str
    data_axis: Optional[str]
    backend: str
    mesh: Optional[Mesh]
    algorithm: str
    capacity: int
    out_capacity: int
    algo_kw: tuple

    @property
    def mesh_shape(self) -> Optional[tuple]:
        return tuple(s for _, s in self.axes) if self.axes else None

    def body(self):
        """The per-PE body this plan runs (:func:`_sort_body`)."""
        return _sort_body(self.axis, self.p, self.algorithm, self.capacity,
                          self.out_capacity, self.algo_kw)


def _plan(shape, cfg: SortConfig) -> _Plan:
    """Check ``cfg`` against keys of ``shape`` and settle the sort it runs.

    ``psort``, each attempt of the fault lane (its current ``p`` and
    ``mesh_shape`` set in ``cfg``) and ``trace_collectives`` plan here, so
    the topology, the algorithm, the capacities and the algorithm keywords
    are worked out in one place."""
    p, algorithm, mesh, backend = cfg.p, cfg.algorithm, cfg.mesh, cfg.backend
    axis, data_axis, mesh_axes = cfg.axis, cfg.data_axis, cfg.mesh_axes
    mesh_shape, levels, external = cfg.mesh_shape, cfg.levels, cfg.external
    if levels is not None and algorithm not in ("auto", "rams", "ntb-ams"):
        raise ValueError(f"levels= applies to the multi-level AMS family "
                         f"(or 'auto'), not algorithm={algorithm!r}")
    if len(shape) not in (1, 2):
        raise ValueError(f"keys must be 1-D (one sort) or 2-D (a batch of "
                         f"independent sorts); got shape {tuple(shape)}")
    batched = len(shape) == 2
    d, n = (shape[0] if batched else 1), shape[-1]
    axes = None
    if mesh_shape is not None:
        p_o, p_i = mesh_shape
        if (p_o & (p_o - 1)) or (p_i & (p_i - 1)) or p_o < 1 or p_i < 1:
            raise ValueError(f"mesh_shape={mesh_shape} entries must be "
                             f"powers of two (hypercube layout)")
        if p is not None and p != p_o * p_i:
            raise ValueError(f"p={p} inconsistent with mesh_shape="
                             f"{mesh_shape}")
        p = p_o * p_i
        axes = ((mesh_axes[0], p_o), (mesh_axes[1], p_i))
        if backend == "shard_map":
            if mesh is None:
                from repro.dist.sharding import sort_mesh
                mesh = sort_mesh(shape=mesh_shape, d=d, data_axis=data_axis,
                                 mesh_axes=mesh_axes)
            want = dict(axes)
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
    if cfg.fault_policy is not None and backend != "sim":
        raise ValueError("fault_policy= requires backend='sim' (the "
                         "fault-injection lane runs on emulated PEs)")

    per = -(-max(n, 1) // p)                       # ceil(n/p)
    capacity = max(4, int(np.ceil(per * cfg.capacity_factor)))
    if algorithm == "auto":
        algorithm = selection.select_algorithm(
            n, p, model=cfg.cost_model, levels=levels, mesh_shape=mesh_shape,
            budget=external.budget if external is not None else None)
    # the shards stream whenever they outgrow the budget: a fault-lane
    # rescale shrinks p, so an attempt that started in-core can go external
    # (and never the other way around)
    if external is not None and (algorithm == "external"
                                 or per > external.budget):
        algorithm = "external"
    algo_kw = dict(cfg.algo_kw)
    if cfg.overlap and algorithm in _OVERLAP_ALGOS:
        algo_kw.setdefault("overlap", True)
    if algorithm in ("rams", "ntb-ams"):
        if axes is not None:
            from .rams import nested_level_bits
            algo_kw.setdefault(
                "level_bits", tuple(nested_level_bits(p_o, p_i, levels)))
        elif levels is not None:
            algo_kw.setdefault("levels", levels)
    # the gather algorithms concentrate the whole output on PE 0
    out_capacity = max(1, p * per) if algorithm in ("gatherm", "allgatherm") \
        else capacity
    pe_lead = tuple(s for _, s in axes) if axes else (p,)
    pe_names = tuple(a for a, _ in axes) if axes else (axis,)
    return _Plan(
        n=n, d=d, batched=batched, p=p, per=per,
        lead=((d,) if batched else ()) + pe_lead,
        names=((data_axis,) if batched else ()) + pe_names,
        axes=axes, axis=axis, data_axis=data_axis if batched else None,
        backend=backend, mesh=mesh, algorithm=algorithm, capacity=capacity,
        out_capacity=out_capacity, algo_kw=tuple(sorted(algo_kw.items())))


def _sim_runner(plan: _Plan, impl=None):
    """The per-PE body over ``plan.lead`` on emulated PEs
    (:func:`repro.core.comm.sim_map`), its collectives through ``impl``
    (default: resolved from the ambient scope when it runs)."""
    return comm.sim_map(plan.body(), plan.axis, plan.p, impl=impl,
                        nested=plan.axes,
                        mesh=(plan.d, plan.p) if plan.batched else None,
                        data_axis=plan.data_axis)


@partial(jax.jit, static_argnames=("plan", "pallas"))
def _device_program(keys_nd, counts_nd, *, plan: _Plan, pallas):
    """psort's device program: the per-PE body run by ``shard_map`` over
    the mesh axes ``plan.names`` (one PE a block), or by :func:`_sim_runner`
    on the sim backend.

    On a nested mesh the body's collectives name the virtual ``plan.axis``
    and the :func:`repro.core.comm.nested` scope decomposes them onto the
    real axes while tracing.  ``pallas`` (the local-kernel policy) is a
    cache key only: the policy is read at trace time, so without it a
    cached executable would silently ignore a toggle between calls."""
    if plan.backend == "sim":
        return _sim_runner(plan)(keys_nd, counts_nd)
    body = plan.body()
    nlead = len(plan.lead)

    def blk(keys_blk, count_blk):
        with (comm.nested(plan.axis, plan.axes) if plan.axes
              else contextlib.nullcontext()):
            outs = body(keys_blk.reshape(keys_blk.shape[nlead:]),
                        count_blk.reshape(()))
        return tuple(jnp.expand_dims(v, tuple(range(nlead))) for v in outs)

    spec = P(*plan.names)
    return jax.shard_map(blk, mesh=plan.mesh, in_specs=(spec, spec),
                         out_specs=(spec,) * 4, check_vma=False)(keys_nd,
                                                                 counts_nd)


def _layout(u, plan: _Plan):
    """The device program's inputs: ``u`` padded to p·per keys a row and
    shaped ``plan.lead + (per,)``, and each PE's count of real keys."""
    p, per, n = plan.p, plan.per, plan.n
    row_counts = jnp.minimum(jnp.maximum(n - per * jnp.arange(p), 0),
                             per).astype(jnp.int32)
    flat = jnp.full(u.shape[:-1] + (p * per,), pad_value(u.dtype), u.dtype)
    flat = flat.at[..., :n].set(u)
    pe_lead = plan.lead[1:] if plan.batched else plan.lead
    return (flat.reshape(plan.lead + (per,)),
            jnp.broadcast_to(row_counts.reshape(pe_lead), plan.lead))


def _pull(outs, plan: _Plan):
    """The device program's outputs on the host, shaped ``(d, p, ...)``."""
    return [np.asarray(o).reshape((plan.d, plan.p) + o.shape[len(plan.lead):])
            for o in outs]


def _answer_pes(plan: _Plan) -> range:
    """The PEs whose outputs make up the answer: allgatherm leaves the
    whole answer on every PE, so PE 0's alone."""
    return range(1) if plan.algorithm == "allgatherm" else range(plan.p)


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
            cfg = _resolve_external(
                _coerce_config(config, legacy, caller="psort"))
            keys = jnp.asarray(keys)
            plan = _plan(keys.shape, cfg)
            u = key_to_uint(keys)
            in_core = cfg.fault_policy is None and plan.algorithm != "external"
            if in_core:
                outs = _device_program(*_layout(u, plan), plan=plan,
                                       pallas=local_kernels())
        if cfg.fault_policy is not None:
            return _psort_faulty(u, keys.dtype, cfg, plan, return_info)
        if not in_core:
            return _psort_external(u, keys.dtype, cfg, plan, return_info)
        return _collect(outs, plan, keys.dtype, return_info)


def _collect(outs, plan: _Plan, orig_dtype, return_info: bool):
    """The in-core tail: wait for the device program, pull its padded
    per-PE outputs and assemble the answer from them."""
    keys, idx, counts, overflow = outs
    outs = (keys, counts) + ((idx, overflow) if return_info else ())
    with jax.profiler.TraceAnnotation("psort.wait"):
        jax.block_until_ready(outs)
    with jax.profiler.TraceAnnotation("psort.pull",
                                      bytes=sum(o.nbytes for o in outs)):
        outs = _pull(outs, plan)
    keys, counts = outs[:2]
    idx, overflow = outs[2:] if return_info else (None, None)
    answer_bytes = int(counts[:, _answer_pes(plan)].sum()) \
        * np.dtype(orig_dtype).itemsize
    with jax.profiler.TraceAnnotation("psort.assemble", bytes=answer_bytes):
        return _assemble(keys, idx, counts, overflow, plan, orig_dtype,
                         return_info)


def _assemble(keys, idx, counts, overflow, plan: _Plan, orig_dtype,
              return_info: bool, extra_info=None):
    """``psort``'s result from the host outputs of every path — keys and
    idx ``(d, p, out_cap)``, counts and overflow ``(d, p)`` — and, with
    ``return_info``, its info dict, ``extra_info`` added."""
    n, d, p = plan.n, plan.d, plan.p
    rows = [np.concatenate([keys[r, i, :counts[r, i]]
                            for i in _answer_pes(plan)]) for r in range(d)]
    result = uint_to_key(jnp.asarray(np.stack(rows) if plan.batched
                                     else rows[0]), orig_dtype)
    if not return_info:
        return result
    perms = [np.concatenate([idx[r, i, :counts[r, i]] for i in range(p)])
             if n else np.zeros((0,), np.uint32) for r in range(d)]
    info = {
        "algorithm": plan.algorithm,
        "backend": plan.backend,
        "mesh_shape": plan.mesh_shape,
        "counts": counts if plan.batched else counts[0],
        "overflow": int(np.asarray(overflow).sum()),
        "balance": counts.max() / max(1.0, n / p),
        "perm": np.stack(perms) if plan.batched else perms[0],
        "n": n,
        "d": d,
        **(extra_info or {}),
    }
    return result, info


def _resolve_external(cfg: SortConfig) -> SortConfig:
    """Explicit policy wins; else ``REPRO_EXTERNAL_BUDGET`` (sim only)."""
    env = os.environ.get("REPRO_EXTERNAL_BUDGET")
    if cfg.external is not None or not env or cfg.backend != "sim":
        return cfg
    from .external import ExternalPolicy
    return cfg.replace(external=ExternalPolicy(budget=int(env)))


def _psort_external(u, orig_dtype, cfg: SortConfig, plan: _Plan,
                    return_info: bool):
    """The non-fault ``psort(..., external=...)`` tail: run the four
    external passes once and assemble the answer like the in-core path.
    Ambient collectives decorators (``comm.counting()``) apply — the passes
    resolve ``impl`` per ``sim_map`` call."""
    from .external import _psort_external_once
    policy = cfg.external
    outs = _psort_external_once(u, plan.n, axis=plan.axis, p=plan.p,
                                policy=policy, impl=None, overlap=cfg.overlap)
    return _assemble(*outs, plan, orig_dtype, return_info, {"external": {
        "budget": policy.budget,
        "runs": max(1, -(-plan.per // policy.budget)),
        "merge": policy.merge,
    }})


def _psort_faulty(u, orig_dtype, cfg: SortConfig, plan: _Plan,
                  return_info: bool):
    """The ``psort(..., fault_policy=...)`` driver (sim backend).

    Attempt loop (bounded by ``repro.runtime.failures.run_with_restarts``):
    plan the sort at the current topology and trace it afresh under
    ``FaultyCollectives`` executing the policy's surviving
    :class:`repro.core.comm.FaultPlan` — a fresh ``jax.jit`` each time,
    since injection and counting act at trace time and a cached executable
    would replay nothing.  On a :class:`repro.core.comm.PEFailure` — raised
    by a fired kill, or by this function for a watchdog-flagged straggler —
    exclude the PE, plan the reduced topology
    (``elastic.plan_sort_rescale``), record a ``rescale`` trace event
    carrying the new extent, and retry.  Progress = shrinking p, so a
    rescale that fails to shrink trips the loop's no-progress give-up
    rather than burning the restart budget.
    """
    from repro.runtime.elastic import plan_sort_rescale
    from repro.runtime.failures import flag_stragglers, run_with_restarts

    policy = cfg.fault_policy
    trace = policy.trace if policy.trace is not None else comm.CommTrace()
    policy.trace = trace
    log = policy.logger if policy.logger is not None else (lambda *a: None)
    faults = policy.plan if policy.plan is not None else comm.FaultPlan()
    if not isinstance(faults, comm.FaultPlan):
        faults = comm.FaultPlan(tuple(faults))
    state = {"p": plan.p, "mesh_shape": plan.mesh_shape, "faults": faults,
             "failed": ()}
    policy.attempts.clear()

    def attempt(_start):
        # algorithm="auto" is re-selected at the attempt's p, and overlap
        # applies to what that picks
        att = _plan(u.shape, cfg.replace(p=state["p"],
                                         mesh_shape=state["mesh_shape"]))
        rec = {"p": att.p, "mesh_shape": att.mesh_shape,
               "algorithm": att.algorithm, "ok": False}
        policy.attempts.append(rec)
        # faulty outside counting: a killed launch records its fault:kill
        # event but not the launch the dead PE never completed
        fc = comm.FaultyCollectives(
            comm.CountingCollectives(comm.SIM, trace), state["faults"], trace)
        if att.algorithm == "external":
            from .external import _psort_external_once
            out = _psort_external_once(u, att.n, axis=att.axis, p=att.p,
                                       policy=cfg.external, impl=fc,
                                       overlap=cfg.overlap)
        else:
            out = _pull(jax.jit(_sim_runner(att, fc))(*_layout(u, att)), att)
        times = [policy.base_step_time * fc.fired_delays.get(pe, 1.0)
                 for pe in range(att.p)]
        slow = flag_stragglers(times, k_mad=policy.k_mad,
                               warmup=policy.warmup)
        if slow:
            raise comm.PEFailure(slow[0], phase="straggler")
        rec["ok"] = True
        return out, att

    def rescale(e, restarts):
        p_cur, ms = state["p"], state["mesh_shape"]
        rplan = plan_sort_rescale(p_cur, (e.pe,), mesh_shape=ms)
        trace.add("rescale", 0, rplan.p_new, axis=plan.axis, tag=e.phase,
                  pe=e.pe)
        why = "straggling" if e.phase == "straggler" else "failed"
        log(f"[psort] PE {e.pe} {why} at p={p_cur}; "
            f"rescaling to p={rplan.p_new}")
        state["p"] = rplan.p_new
        state["mesh_shape"] = rplan.mesh_shape
        state["faults"] = state["faults"].surviving(e.pe, rplan.p_new)
        state["failed"] += (e.pe,)

    out, fin = run_with_restarts(
        attempt, max_restarts=policy.max_restarts, retry_on=(comm.PEFailure,),
        on_failure=rescale, progress_fn=lambda: -state["p"], logger=log)
    return _assemble(*out, fin, orig_dtype, return_info, {
        "fault": {
            "p_final": fin.p,
            "failed": state["failed"],
            "restarts": len(policy.attempts) - 1,
            "attempts": list(policy.attempts),
        },
        "comm_trace": trace,
    })


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
    # the trace runs on emulated PEs whatever backend the config names
    plan = _plan((d, n) if d > 1 else (n,),
                 cfg.replace(backend="sim", mesh=None))
    counter = comm.CountingCollectives(comm.SIM)
    if cfg.external is not None:
        from .external import _psort_external_once
        rng = np.random.default_rng(0xE87)
        u = jnp.asarray(rng.integers(0, 2 ** 32, size=max(n, 1),
                                     dtype=np.int64).astype(np.uint32))
        _psort_external_once(u, n, axis=plan.axis, p=plan.p,
                             policy=cfg.external, impl=counter,
                             overlap=cfg.overlap)
    else:
        jax.eval_shape(_sim_runner(plan, impl=counter),
                       jax.ShapeDtypeStruct(plan.lead + (plan.per,),
                                            jnp.uint32),
                       jax.ShapeDtypeStruct(plan.lead, jnp.int32))
    return counter.trace
