"""Collectives runtime: one interface, two execution backends, one decorator.

Every communication primitive the sorting library uses (``ppermute``,
``psum``, ``all_gather``, ``all_to_all``, ``axis_index`` and their grouped
variants) is routed through the module-level functions below, which dispatch
to the *current* :class:`Collectives` implementation:

  * :class:`LaxCollectives` — the production path: thin forwarding to
    ``jax.lax``; valid inside ``shard_map`` over real (or emulated host)
    devices.  This is the default.

  * :class:`SimCollectives` — the **simulation backend**: the same algorithm
    bodies are evaluated over a leading PE axis in a single process with
    ``jax.vmap(body, axis_name=...)`` (see :func:`sim_map`).  vmap's
    batching rules implement the ungrouped collectives natively; the grouped
    variants (``axis_index_groups``), which vmap does not support, are
    implemented here from static group-index tables.  Small groups use one
    full ``all_gather`` + table lookup; once the batched gather buffer would
    exceed ``chunk_bytes`` (the p² blow-up that kept the sim backend under
    p = 256), the same result is produced *chunked*: a ``lax.scan`` ring of
    ``ppermute`` steps moves one PE block per iteration, so peak memory is
    the output size O(p·g) instead of O(p²).  This lifts the sim backend to
    p = 1024 emulated PEs in one process.  :func:`sim_map` also has a
    ``mesh=(d, p)`` mode emulating a 2-D (data × sort) device mesh: the
    data axis is an outer vmap, and every collective resolves within the
    row's p-sized sort subgroup.

  * :class:`CountingCollectives` — a decorator backend: wraps any
    ``Collectives``, forwards every call unchanged, and records a structured
    :class:`CommTrace` (per-primitive launch counts, payload bytes per PE,
    group sizes, target axis, phase tag).  ``benchmarks/calibrate.py`` fits
    the machine profile of ``core/selection.py`` from these traces;
    :func:`counting` scopes one.

  * :class:`FaultyCollectives` — a decorator backend (mirroring
    :class:`CountingCollectives`) that executes a deterministic
    :class:`FaultPlan` while the body is traced: a planned *kill* raises a
    structured :class:`PEFailure` at the first collective of the matching
    phase tag (the way a dead participant aborts a fused collective for
    its whole group), a planned *delay* records a stretched simulated step
    time for the watchdog lane.  Composable with :class:`SimCollectives`
    and :class:`CountingCollectives`; injected events are recorded into
    the same :class:`CommTrace` (``fault:kill`` / ``fault:delay``
    pseudo-primitives carrying the target PE, axis and phase tag), which
    is what lets the fault tests assert *where* a fault fired and that the
    rescaled re-run followed (see ``psort(fault_policy=...)`` in
    ``core/api.py``).

  * :class:`NestedCollectives` — a decorator *view*: presents one virtual
    flat axis over an ``(outer, inner)`` pair of real named axes (a
    hierarchical inter-host × intra-host mesh) and decomposes every
    virtual-axis collective element-exactly onto the real axes of the
    wrapped backend — so the unchanged algorithm bodies run over nested
    meshes, bitwise-identical to the flat-axis path, on both the Lax and
    Sim backends (:func:`nested` scopes the shard_map side;
    ``sim_map(nested=...)`` the simulated side).

Backends are scoped with :func:`use` (a context manager); the scope must be
active while the algorithm body is *traced*, so backend runners like
:func:`sim_map` enter it inside their traced wrapper.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Collectives:
    """Interface of the named-axis collectives the library relies on.

    Every method takes an ``axis_name`` and resolves **relative to that
    named axis only** — never to the full device set.  On a multi-axis
    mesh (say ``("data", "sort")``), ``axis_index(x, "sort")`` is the
    position *within* the sort axis, ``all_gather(x, "sort")`` gathers the
    ``mesh.shape["sort"]`` participants that share this PE's data-axis
    coordinate, and ``axis_index_groups`` lists indices *along the named
    axis* (per the ``jax.lax`` contract), so one grouped collective runs
    independently inside every subgroup of every data-axis slice.  This is
    what lets the sorting algorithms — which only ever receive
    ``(axis_name, p)`` with ``p`` = the sort-axis size — run unchanged
    within named subgroups of a 2-D mesh (see :func:`sim_map`'s ``mesh=``
    mode and ``psort`` on batched inputs).

    Implementations:

    * :class:`LaxCollectives` — forwards to ``jax.lax`` (named-axis
      resolution is the ``shard_map`` semantics);
    * :class:`SimCollectives` — the same semantics under ``jax.vmap``
      with grouped variants built from static tables;
    * :class:`CountingCollectives` — forwards to another backend and
      records a :class:`CommTrace`.
    """

    name = "abstract"

    def axis_index(self, axis_name):
        raise NotImplementedError

    def ppermute(self, x, axis_name, perm):
        raise NotImplementedError

    def psum(self, x, axis_name, axis_index_groups=None):
        raise NotImplementedError

    def all_gather(self, x, axis_name, axis_index_groups=None, tiled=False):
        raise NotImplementedError

    def all_to_all(self, x, axis_name, split_axis=0, concat_axis=0,
                   axis_index_groups=None, tiled=False):
        raise NotImplementedError

    def alltoall_stream(self, x, axis_name, fold, init, gsize,
                        axis_index_groups=None):
        """Chunk-granular all_to_all: fold per-source blocks as they arrive.

        ``x`` is a pytree of tiled per-destination buffers — every leaf has
        ``shape[0]`` divisible by ``gsize``, laid out exactly like the input
        of ``all_to_all(split_axis=0, concat_axis=0, tiled=True)``.  Instead
        of returning the gathered buffer, the received data is delivered one
        *source block* at a time: ``fold(carry, chunk, src)`` consumes the
        block sent by group member ``src`` (a traced int32 group rank;
        ``chunk`` leaves have shape ``(shape[0] // gsize, ...)``) and returns
        the updated carry.  Returns the final carry.

        Delivery-order contract: every source is delivered exactly once;
        sources in ``[0, my_rank)`` arrive in ascending order, as do sources
        in ``[my_rank, gsize)`` — the interleaving of the two runs is
        implementation-defined (the ring implementations start at own rank
        and wrap, the barrier fallback folds ``0..gsize-1``).  Consumers
        must therefore be insensitive to the interleaving; the two-run
        incremental merge in ``hypercube._alltoall_route(stream=True)`` is
        the canonical such fold.

        This default implementation is the *barrier* fallback: one regular
        ``all_to_all``, then the blocks folded in ascending source order —
        bitwise-identical to any conforming streaming implementation, and
        inherited by backends without a chunked path (e.g.
        :class:`NestedCollectives`).
        """
        recv = self.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                               axis_index_groups=axis_index_groups,
                               tiled=True)
        carry = init
        for s in range(gsize):
            chunk = jax.tree.map(
                lambda v, s=s: v[s * (v.shape[0] // gsize):
                                 (s + 1) * (v.shape[0] // gsize)], recv)
            carry = fold(carry, chunk, jnp.int32(s))
        return carry

    def _stream_ring(self, x, axis_name, fold, init, gsize,
                     axis_index_groups=None):
        """Shared ring-scan ``alltoall_stream``: a ``lax.scan`` carries the
        rotating send buffer (one ``ppermute`` per step, exactly the chunked
        ring of ``SimCollectives``), and each step folds the block that just
        arrived — at step t my block of group member (rank + t) mod g.  Used
        by :class:`LaxCollectives` and :class:`SimCollectives`; delivery
        starts at own rank and wraps, satisfying the two-ascending-runs
        contract."""
        for v in jax.tree.leaves(x):
            assert v.shape[0] % gsize == 0, (v.shape, gsize)
        if axis_index_groups is None or \
                _is_full_identity_group(axis_index_groups):
            perm = [((i + 1) % gsize, i) for i in range(gsize)]
            r = self.axis_index(axis_name).astype(jnp.int32)
        else:
            members, rank = _group_tables(axis_index_groups)
            assert members.shape[1] == gsize, (members.shape, gsize)
            perm = _ring_perm(members, rank)
            r = jnp.take(jnp.asarray(rank),
                         self.axis_index(axis_name)).astype(jnp.int32)

        def slice_mine(v):
            blk = v.shape[0] // gsize
            return jax.lax.dynamic_slice_in_dim(v, r * blk, blk, axis=0)

        def step(carry, t):
            buf, acc = carry
            chunk = jax.tree.map(slice_mine, buf)
            acc = fold(acc, chunk, ((r + t) % gsize).astype(jnp.int32))
            buf = jax.tree.map(
                lambda v: self.ppermute(v, axis_name, perm), buf)
            return (buf, acc), None

        (_, acc), _ = jax.lax.scan(step, (x, init),
                                   jnp.arange(gsize, dtype=jnp.int32))
        return acc


class LaxCollectives(Collectives):
    """Forward to ``jax.lax`` — the shard_map / real-device path."""

    name = "shard_map"

    def axis_index(self, axis_name):
        return jax.lax.axis_index(axis_name)

    def ppermute(self, x, axis_name, perm):
        return jax.lax.ppermute(x, axis_name, perm)

    def psum(self, x, axis_name, axis_index_groups=None):
        return jax.lax.psum(x, axis_name, axis_index_groups=axis_index_groups)

    def all_gather(self, x, axis_name, axis_index_groups=None, tiled=False):
        return jax.lax.all_gather(x, axis_name,
                                  axis_index_groups=axis_index_groups,
                                  tiled=tiled)

    def all_to_all(self, x, axis_name, split_axis=0, concat_axis=0,
                   axis_index_groups=None, tiled=False):
        return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                                  concat_axis=concat_axis,
                                  axis_index_groups=axis_index_groups,
                                  tiled=tiled)

    def alltoall_stream(self, x, axis_name, fold, init, gsize,
                        axis_index_groups=None):
        # lax.scan carries the rotating buffer; one ppermute per step.
        return self._stream_ring(x, axis_name, fold, init, gsize,
                                 axis_index_groups=axis_index_groups)


# ---------------------------------------------------------------------------
# Instrumentation: CommTrace + CountingCollectives
# ---------------------------------------------------------------------------


def _payload_bytes(x) -> int:
    """Static per-PE payload size of a pytree (works on tracers)."""
    total = 0
    for leaf in jax.tree.leaves(x):
        shape = jnp.shape(leaf)
        dtype = np.dtype(jnp.result_type(leaf))
        total += int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    return total


@dataclasses.dataclass(frozen=True)
class CommEvent:
    """One collective launch as seen at the call site (per PE).

    ``primitive`` is one of the four collectives for regular launches;
    fault-lane records use the pseudo-primitives ``fault:kill`` /
    ``fault:delay`` (:class:`FaultyCollectives`) and ``rescale`` (the
    ``psort`` fault driver, with ``group_size`` = the post-rescale p).
    ``pe`` identifies the PE an injected event targeted (regular launches
    leave it ``None`` — the trace is per-PE already).
    """
    primitive: str                    # ppermute | psum | all_gather | all_to_all
    bytes: int                        # payload bytes moved per PE (input side)
    group_size: Optional[int] = None  # participants; None = the full axis
    axis: Optional[str] = None        # mesh axis the launch targeted
    tag: Optional[str] = None         # algorithm phase (see :func:`tagged`)
    pe: Optional[int] = None          # target PE of an injected fault event


class CommTrace:
    """Structured record of every collective launched while tracing a body.

    The counts are *trace-time* quantities: one event per call site
    execution, with payload sizes read off the static shapes.  Unrolled
    loops therefore contribute one event per iteration — exactly the launch
    count the α-terms of the cost model charge for.

    Each event carries the mesh axis it targeted and the active phase tag
    (:func:`tagged` — RAMS labels its shuffle and every level).  Under a
    :class:`NestedCollectives` view the recorded axes are the *real* mesh
    axes of the decomposed launches, so :meth:`by_axis` splits inter- from
    intra-axis volume and :meth:`by_tag` attributes it per level.
    """

    def __init__(self):
        self.events: List[CommEvent] = []

    def add(self, primitive: str, nbytes: int,
            group_size: Optional[int] = None, axis: Optional[str] = None,
            tag: Optional[str] = None, pe: Optional[int] = None):
        self.events.append(CommEvent(primitive, int(nbytes), group_size,
                                     axis, tag, pe))

    # -- aggregation ------------------------------------------------------

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.events:
            out[e.primitive] = out.get(e.primitive, 0) + 1
        return out

    def payload_bytes(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.events:
            out[e.primitive] = out.get(e.primitive, 0) + e.bytes
        return out

    PRIMITIVES = ("ppermute", "psum", "all_gather", "all_to_all")

    def injected(self) -> List[CommEvent]:
        """Injected fault-lane records (``fault:*`` / ``rescale``) — kept
        out of every launch/byte aggregate so a faulted trace still fits
        the cost model; the fault tests read them directly."""
        return [e for e in self.events if e.primitive not in self.PRIMITIVES]

    @property
    def launches(self) -> int:
        return sum(1 for e in self.events if e.primitive in self.PRIMITIVES)

    @property
    def p2p_launches(self) -> int:
        """Point-to-point steps (collective-permutes) — the α term."""
        return sum(1 for e in self.events if e.primitive == "ppermute")

    @property
    def fused_launches(self) -> int:
        """Hardware-routed fused collectives — the α_c term."""
        return self.launches - self.p2p_launches

    def fused_hops(self, p: int) -> float:
        """Σ over fused launches of the torus pipeline depth (group p)^⅓ —
        the α_hop term of the v5e-style model in ``core/selection.py``."""
        return float(sum((e.group_size or p) ** (1.0 / 3.0)
                         for e in self.events
                         if e.primitive in self.PRIMITIVES
                         and e.primitive != "ppermute"))

    IO_PRIMITIVES = ("ext:h2d", "ext:d2h")

    def wire_bytes(self) -> int:
        # injected events (fault records, external-lane I/O) never count
        # toward the on-wire volume the cost model's beta is fitted from
        return sum(e.bytes for e in self.events
                   if e.primitive in self.PRIMITIVES)

    def io_bytes(self) -> int:
        """Host↔device streaming volume of the external lane — the
        ``ext:h2d`` / ``ext:d2h`` pseudo-events the out-of-core driver
        injects around its copies (they are not collectives, so they stay
        out of :attr:`launches` / :meth:`wire_bytes`; the ``io_beta`` cost
        term is fitted against this aggregate)."""
        return sum(e.bytes for e in self.events
                   if e.primitive in self.IO_PRIMITIVES)

    # -- axis / phase attribution ----------------------------------------

    def filter(self, primitive: Optional[str] = None,
               axis: Optional[str] = None,
               tag: Optional[str] = None) -> "CommTrace":
        """Sub-trace of the events matching every given criterion
        (``None`` criteria are ignored; ``axis=""`` / ``tag=""`` select
        events with the field unset)."""
        sub = CommTrace()
        for e in self.events:
            if primitive is not None and e.primitive != primitive:
                continue
            if axis is not None and (e.axis or "") != axis:
                continue
            if tag is not None and (e.tag or "") != tag:
                continue
            sub.events.append(e)
        return sub

    def axes(self) -> List[str]:
        return sorted({e.axis or "" for e in self.events})

    def tags(self) -> List[str]:
        return sorted({e.tag or "" for e in self.events})

    def by_axis(self) -> Dict[str, dict]:
        """Per-mesh-axis launch/byte totals — under a nested view this is
        the inter- vs. intra-axis communication split."""
        return {a: self.filter(axis=a).summary() for a in self.axes()}

    def by_tag(self) -> Dict[str, dict]:
        """Per-phase totals (RAMS: ``shuffle``, ``level0``, ``level1``, …).
        The tags partition the events, so the per-tag summaries sum back to
        :meth:`summary` — the per-level attribution invariant."""
        return {t: self.filter(tag=t).summary() for t in self.tags()}

    def summary(self, p: Optional[int] = None) -> dict:
        s = {
            "launches": self.launches,
            "p2p_launches": self.p2p_launches,
            "fused_launches": self.fused_launches,
            "counts": self.counts(),
            "bytes": self.payload_bytes(),
            "wire_bytes": self.wire_bytes(),
        }
        if p is not None:
            s["fused_hops"] = self.fused_hops(p)
        return s


# Phase tag recorded onto CommEvents (e.g. "shuffle", "level0").  A
# ContextVar for the same reason as the backend scope: tags are read at
# trace time and must be per-thread.
_TAG: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "repro_comm_tag", default=None)


@contextlib.contextmanager
def tagged(tag: Optional[str]):
    """Label every collective traced in this scope with an algorithm-phase
    tag (recorded by :class:`CountingCollectives`), and every op with it as
    a ``jax.named_scope`` (the ``op_name`` a profile shows).
    RAMS tags its initial shuffle and each level, which is what lets a
    counted trace attribute launches/bytes per level."""
    token = _TAG.set(tag)
    try:
        with (contextlib.nullcontext() if tag is None
              else jax.named_scope(tag)):
            yield
    finally:
        _TAG.reset(token)


def current_tag() -> Optional[str]:
    return _TAG.get()


class CountingCollectives(Collectives):
    """Decorator backend: forward to ``inner``, record a :class:`CommTrace`.

    Wraps *any* backend (sim or shard_map), so the same counted trace is
    available whichever way the body executes.  Records the collective as
    issued at the call site — e.g. one grouped all_gather is one fused
    launch regardless of how :class:`SimCollectives` emulates it.  Each
    event carries the axis name the launch targeted and the active
    :func:`tagged` phase; under a :class:`NestedCollectives` view, place
    the counter *inside* the view (``NestedCollectives(inner=counter)``)
    to record the decomposed per-real-axis launches.
    """

    def __init__(self, inner: Collectives, trace: Optional[CommTrace] = None):
        self.inner = inner
        self.trace = trace if trace is not None else CommTrace()
        self.name = f"counting({inner.name})"

    @staticmethod
    def _gsize(axis_index_groups) -> Optional[int]:
        if axis_index_groups is None:
            return None
        return len(list(list(axis_index_groups)[0]))

    def axis_index(self, axis_name):
        return self.inner.axis_index(axis_name)       # not a communication

    def ppermute(self, x, axis_name, perm):
        self.trace.add("ppermute", _payload_bytes(x), axis=axis_name,
                       tag=_TAG.get())
        return self.inner.ppermute(x, axis_name, perm)

    def psum(self, x, axis_name, axis_index_groups=None):
        self.trace.add("psum", _payload_bytes(x),
                       self._gsize(axis_index_groups), axis=axis_name,
                       tag=_TAG.get())
        return self.inner.psum(x, axis_name,
                               axis_index_groups=axis_index_groups)

    def all_gather(self, x, axis_name, axis_index_groups=None, tiled=False):
        self.trace.add("all_gather", _payload_bytes(x),
                       self._gsize(axis_index_groups), axis=axis_name,
                       tag=_TAG.get())
        return self.inner.all_gather(x, axis_name,
                                     axis_index_groups=axis_index_groups,
                                     tiled=tiled)

    def all_to_all(self, x, axis_name, split_axis=0, concat_axis=0,
                   axis_index_groups=None, tiled=False):
        self.trace.add("all_to_all", _payload_bytes(x),
                       self._gsize(axis_index_groups), axis=axis_name,
                       tag=_TAG.get())
        return self.inner.all_to_all(x, axis_name, split_axis=split_axis,
                                     concat_axis=concat_axis,
                                     axis_index_groups=axis_index_groups,
                                     tiled=tiled)

    def alltoall_stream(self, x, axis_name, fold, init, gsize,
                        axis_index_groups=None):
        # One event per delivered chunk, tagged ``ovl:<phase>`` — the gsize
        # chunk events sum exactly to the barrier path's single all_to_all
        # event for the same buffers (every leaf's shape[0] divides gsize).
        # Recorded here rather than inside the ring: the scan body traces
        # once, so counting the inner ppermutes would record one launch.
        per_chunk = _payload_bytes(x) // max(int(gsize), 1)
        tag = f"ovl:{_TAG.get() or ''}"
        for _ in range(int(gsize)):
            self.trace.add("all_to_all", per_chunk,
                           self._gsize(axis_index_groups), axis=axis_name,
                           tag=tag)
        return self.inner.alltoall_stream(x, axis_name, fold, init, gsize,
                                          axis_index_groups=axis_index_groups)


@contextlib.contextmanager
def counting(inner: Optional[Collectives] = None):
    """Scope a counting decorator over ``inner`` (default: current backend);
    yields the :class:`CommTrace` being filled.  Must wrap *tracing* — a
    jit cache hit records nothing.  A ``counting()`` scope survives entry
    into :func:`sim_map`: the runner re-wraps its sim backend with the
    same trace, so ``with comm.counting() as tr: psort(..., backend="sim")``
    records the simulated run's collectives."""
    cc = CountingCollectives(inner if inner is not None else current())
    with use(cc):
        yield cc.trace


# ---------------------------------------------------------------------------
# Fault injection: PEFailure + FaultPlan + FaultyCollectives
# ---------------------------------------------------------------------------


class PEFailure(RuntimeError):
    """A (simulated) PE died mid-collective.

    Raised **at trace time** by :class:`FaultyCollectives` when a planned
    kill fires, aborting the traced computation the way a dead participant
    aborts a fused collective for its whole group.  Carries the identity
    the rescale path needs (``repro.runtime.elastic.plan_sort_rescale``):
    the flat PE rank, the phase tag, and the primitive/axis of the launch
    that observed the failure.  The ``psort`` fault driver also raises it
    with ``phase="straggler"`` to route a watchdog-flagged PE down the
    same exclude-and-rescale path.
    """

    def __init__(self, pe: int, phase: Optional[str] = None,
                 primitive: Optional[str] = None, axis: Optional[str] = None):
        self.pe = int(pe)
        self.phase = phase
        self.primitive = primitive
        self.axis = axis
        super().__init__(
            f"PE {self.pe} failed during {primitive or 'collective'} "
            f"(axis={axis!r}, phase={phase!r})")


@dataclasses.dataclass(frozen=True)
class PEFault:
    """One planned fault: kill or delay PE ``pe``.

    ``tag`` names the phase (:func:`tagged`) whose collectives trigger the
    fault; ``None`` matches any phase, so the fault fires at the first
    collective of the run.  ``after`` skips that many matching launches
    first — the fault fires on the (``after`` + 1)-th.  ``factor`` is the
    simulated step-time stretch of a ``delay`` fault, the straggler signal
    ``repro.runtime.failures.flag_stragglers`` thresholds against
    ``k_mad`` deviations.

    PE indices are flat ranks in the topology of the attempt the fault
    fires in; after a rescale the driver drops plans whose ``pe`` fell off
    the shrunken mesh.
    """

    kind: str                       # "kill" | "delay"
    pe: int
    tag: Optional[str] = None       # phase tag to fire at; None = any
    after: int = 0                  # matching launches to let pass first
    factor: float = 4.0             # step-time stretch of a delay

    def __post_init__(self):
        if self.kind not in ("kill", "delay"):
            raise ValueError(f"unknown fault kind {self.kind!r}")


def kill_pe(pe: int, tag: Optional[str] = None, after: int = 0) -> PEFault:
    """A fault that kills PE ``pe`` at phase ``tag``."""
    return PEFault("kill", int(pe), tag, int(after))


def delay_pe(pe: int, factor: float = 4.0, tag: Optional[str] = None,
             after: int = 0) -> PEFault:
    """A fault that stretches PE ``pe``'s simulated step time ×``factor``."""
    return PEFault("delay", int(pe), tag, int(after), float(factor))


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A deterministic set of :class:`PEFault` to execute during one run."""

    faults: Tuple[PEFault, ...] = ()

    def __bool__(self) -> bool:
        return bool(self.faults)

    def surviving(self, pe: int, p_new: int) -> "FaultPlan":
        """The plan after PE ``pe`` was excluded and the topology shrank
        to ``p_new``: drop its faults and any targeting off-mesh ranks."""
        return FaultPlan(tuple(f for f in self.faults
                               if f.pe != pe and f.pe < p_new))


class FaultyCollectives(Collectives):
    """Decorator backend: forward to ``inner``, executing a ``FaultPlan``.

    Mirrors :class:`CountingCollectives` — wraps any backend and checks
    the plan on every collective launch at trace time.  A matching *kill*
    records a ``fault:kill`` event and raises :class:`PEFailure`; a
    matching *delay* records ``fault:delay`` and accumulates the stretch
    factor in :attr:`fired_delays` (read by the ``psort`` fault driver to
    synthesize per-PE step times for the watchdog lane).  Injected events
    go to ``trace`` — defaulting to the wrapped backend's trace when it is
    a :class:`CountingCollectives`, so one :class:`CommTrace` interleaves
    the injected events with the regular launches per axis/tag.

    Like :func:`counting`, the decorator acts while the body is *traced*:
    a jit cache hit replays neither launches nor faults, so the fault lane
    always executes under a fresh trace (``psort``'s driver jits each
    attempt anew).
    """

    def __init__(self, inner: Collectives, plan: FaultPlan,
                 trace: Optional[CommTrace] = None):
        self.inner = inner
        self.plan = plan if isinstance(plan, FaultPlan) \
            else FaultPlan(tuple(plan))
        if trace is None:
            trace = getattr(inner, "trace", None)
        self.trace = trace if trace is not None else CommTrace()
        self.fired_delays: Dict[int, float] = {}
        self._launches: Dict[PEFault, int] = {}
        self._done: Set[PEFault] = set()
        self.name = f"faulty({inner.name})"

    def _inject(self, primitive: str, axis_name) -> None:
        tag = _TAG.get()
        pending = [f for f in self.plan.faults if f not in self._done
                   and (f.tag is None or f.tag == tag)]
        # kills outrank delays within one launch: the PE dies before its
        # slowdown could be observed
        for f in sorted(pending, key=lambda f: f.kind != "kill"):
            seen = self._launches.get(f, 0) + 1
            self._launches[f] = seen
            if seen <= f.after:
                continue
            self._done.add(f)
            if f.kind == "kill":
                self.trace.add("fault:kill", 0, axis=str(axis_name),
                               tag=tag, pe=f.pe)
                raise PEFailure(f.pe, phase=tag, primitive=primitive,
                                axis=str(axis_name))
            self.trace.add("fault:delay", 0, axis=str(axis_name),
                           tag=tag, pe=f.pe)
            self.fired_delays[f.pe] = max(self.fired_delays.get(f.pe, 1.0),
                                          f.factor)

    def axis_index(self, axis_name):
        return self.inner.axis_index(axis_name)       # not a communication

    def ppermute(self, x, axis_name, perm):
        self._inject("ppermute", axis_name)
        return self.inner.ppermute(x, axis_name, perm)

    def psum(self, x, axis_name, axis_index_groups=None):
        self._inject("psum", axis_name)
        return self.inner.psum(x, axis_name,
                               axis_index_groups=axis_index_groups)

    def all_gather(self, x, axis_name, axis_index_groups=None, tiled=False):
        self._inject("all_gather", axis_name)
        return self.inner.all_gather(x, axis_name,
                                     axis_index_groups=axis_index_groups,
                                     tiled=tiled)

    def all_to_all(self, x, axis_name, split_axis=0, concat_axis=0,
                   axis_index_groups=None, tiled=False):
        self._inject("all_to_all", axis_name)
        return self.inner.all_to_all(x, axis_name, split_axis=split_axis,
                                     concat_axis=concat_axis,
                                     axis_index_groups=axis_index_groups,
                                     tiled=tiled)

    def alltoall_stream(self, x, axis_name, fold, init, gsize,
                        axis_index_groups=None):
        # One logical collective, one injection point: a stream counts as a
        # single launch toward fault-plan ``after`` ordinals, same as the
        # barrier all_to_all it replaces.
        self._inject("all_to_all", axis_name)
        return self.inner.alltoall_stream(x, axis_name, fold, init, gsize,
                                          axis_index_groups=axis_index_groups)


@contextlib.contextmanager
def faulty(plan: FaultPlan, inner: Optional[Collectives] = None):
    """Scope a :class:`FaultyCollectives` over ``inner`` (default: the
    current backend); yields the decorator so the caller can read
    :attr:`FaultyCollectives.fired_delays` afterwards.  Must wrap
    *tracing*, exactly like :func:`counting` — and like a ``counting()``
    scope it survives entry into :func:`sim_map`, which re-wraps its sim
    backend with the same plan state."""
    fc = FaultyCollectives(inner if inner is not None else current(), plan)
    with use(fc):
        yield fc


# ---------------------------------------------------------------------------
# Simulation backend
# ---------------------------------------------------------------------------


def _group_tables(axis_index_groups):
    """Static lookup tables for grouped collectives.

    Returns (members, rank): ``members[i]`` lists the PEs of i's group in
    group order; ``rank[i]`` is i's position within its group.  Groups must
    partition the axis and share one size (the jax.lax contract).
    """
    groups = [list(g) for g in axis_index_groups]
    size = len(groups[0])
    assert all(len(g) == size for g in groups), "groups must be equal-sized"
    p = sum(len(g) for g in groups)
    assert sorted(pe for g in groups for pe in g) == list(range(p)), \
        "groups must partition the axis"
    members = np.zeros((p, size), np.int32)
    rank = np.zeros((p,), np.int32)
    for g in groups:
        for r, pe in enumerate(g):
            members[pe] = g
            rank[pe] = r
    return members, rank


def _is_full_identity_group(axis_index_groups) -> bool:
    groups = [list(g) for g in axis_index_groups]
    if len(groups) != 1:
        return False
    return groups[0] == list(range(len(groups[0])))


def _ring_perm(members: np.ndarray, rank: np.ndarray):
    """Static (source, dest) pairs: every PE receives from its next group
    neighbor (ring order within each group).  Applying it t times hands PE
    of rank r the value of group member (r + t) mod g."""
    p, g = members.shape
    return [(int(members[i][(rank[i] + 1) % g]), i) for i in range(p)]


# Above this batched-buffer size, grouped sim collectives switch from the
# one-shot full all_gather (fast, O(p²·payload) peak memory once vmap
# batches it) to the chunked ring evaluation (O(p·g·payload)).
SIM_CHUNK_BYTES = int(os.environ.get("REPRO_SIM_CHUNK_BYTES", 1 << 28))


class SimCollectives(Collectives):
    """Collectives valid under ``jax.vmap(..., axis_name=...)``.

    Ungrouped primitives delegate to ``jax.lax`` (vmap has batching rules
    for them with semantics identical to shard_map's).  Grouped variants,
    which vmap's collective batching rejects, are built from static group
    tables with three evaluation strategies per leaf:

      * degenerate groups (size 1, or one group in axis order) reduce to
        local ops / the native ungrouped collective;
      * small leaves: one full ``all_gather`` + table lookup (one-shot);
      * large leaves (batched gather > ``chunk_bytes``): a ``lax.scan``
        ring of ``ppermute`` steps — one PE block moves per iteration, so
        the p² buffer never materializes.  Integer results are bit-identical
        to the one-shot path; float grouped psum may differ in summation
        order (ring order instead of group order).
    """

    name = "sim"

    def __init__(self, chunk_bytes: Optional[int] = None):
        self.chunk_bytes = SIM_CHUNK_BYTES if chunk_bytes is None \
            else int(chunk_bytes)

    def _use_ring(self, v, p: int) -> bool:
        # the one-shot path batches an all_gather: (p, p, ...) elements
        return p * p * _payload_bytes(v) > max(0, self.chunk_bytes)

    def axis_index(self, axis_name):
        return jax.lax.axis_index(axis_name)

    def ppermute(self, x, axis_name, perm):
        return jax.lax.ppermute(x, axis_name, perm)

    # -- grouped helpers --------------------------------------------------

    @staticmethod
    def _my_rank(rank, axis_name):
        return jnp.take(jnp.asarray(rank), jax.lax.axis_index(axis_name))

    @staticmethod
    def _ring_parts(v, axis_name, perm, gsize):
        """scan the ring: parts[t] = my group member (rank+t)'s ``v``."""
        def step(carry, _):
            return jax.lax.ppermute(carry, axis_name, perm), carry
        _, parts = jax.lax.scan(step, v, None, length=gsize)
        return parts                                   # (gsize,) + v.shape

    def psum(self, x, axis_name, axis_index_groups=None):
        if axis_index_groups is None or \
                _is_full_identity_group(axis_index_groups):
            return jax.lax.psum(x, axis_name)
        members, rank = _group_tables(axis_index_groups)
        p, gsize = members.shape
        if gsize == 1:
            return x
        perm = _ring_perm(members, rank)

        def one(v):
            if self._use_ring(v, p):
                def step(carry, _):
                    rot, acc = carry
                    rot = jax.lax.ppermute(rot, axis_name, perm)
                    return (rot, acc + rot), None
                (_, acc), _ = jax.lax.scan(step, (v, v), None,
                                           length=gsize - 1)
                return acc
            g = jax.lax.all_gather(v, axis_name)          # (p, ...)
            mine = jnp.take(jnp.asarray(members),
                            jax.lax.axis_index(axis_name), axis=0)
            # dtype= matches lax.psum's dtype-preserving contract (a bare
            # sum promotes int32 → int64 under jax_enable_x64)
            return jnp.sum(jnp.take(g, mine, axis=0), axis=0, dtype=v.dtype)

        return jax.tree.map(one, x)

    def all_gather(self, x, axis_name, axis_index_groups=None, tiled=False):
        if axis_index_groups is None or \
                _is_full_identity_group(axis_index_groups):
            return jax.lax.all_gather(x, axis_name, tiled=tiled)
        members, rank = _group_tables(axis_index_groups)
        p, gsize = members.shape
        if gsize == 1:
            def solo(v):
                return v if tiled else v[None]
            return jax.tree.map(solo, x)
        perm = _ring_perm(members, rank)

        def one(v):
            if self._use_ring(v, p):
                parts = self._ring_parts(v, axis_name, perm, gsize)
                r = self._my_rank(rank, axis_name)
                # group order: out[j] = member j's value = parts[(j-r) mod g]
                idx = (jnp.arange(gsize) - r) % gsize
                out = jnp.take(parts, idx, axis=0)        # (gsize, ...)
            else:
                g = jax.lax.all_gather(v, axis_name)      # (p, ...)
                mine = jnp.take(jnp.asarray(members),
                                jax.lax.axis_index(axis_name), axis=0)
                out = jnp.take(g, mine, axis=0)           # (gsize, ...)
            if tiled:
                out = out.reshape((-1,) + out.shape[2:])
            return out

        return jax.tree.map(one, x)

    def all_to_all(self, x, axis_name, split_axis=0, concat_axis=0,
                   axis_index_groups=None, tiled=False):
        if axis_index_groups is None or \
                _is_full_identity_group(axis_index_groups):
            return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                                      concat_axis=concat_axis, tiled=tiled)
        if split_axis != 0 or concat_axis != 0 or not tiled:
            raise NotImplementedError(
                "sim grouped all_to_all supports tiled split/concat axis 0")
        members, rank = _group_tables(axis_index_groups)
        p, gsize = members.shape
        if gsize == 1:
            return x
        perm = _ring_perm(members, rank)

        def one(v):
            assert v.shape[0] % gsize == 0, (v.shape, gsize)
            blk = v.shape[0] // gsize
            me = jax.lax.axis_index(axis_name)
            if self._use_ring(v, p):
                r = self._my_rank(rank, axis_name)

                def step(carry, _):
                    # carry = buffer of group member (rank + t); its block
                    # destined to me sits at my rank's offset
                    y = jax.lax.dynamic_slice_in_dim(carry, r * blk, blk,
                                                     axis=0)
                    return jax.lax.ppermute(carry, axis_name, perm), y

                _, ys = jax.lax.scan(step, v, None, length=gsize)
                idx = (jnp.arange(gsize) - r) % gsize     # → group order
                out = jnp.take(ys, idx, axis=0)           # (gsize, blk, ...)
                return out.reshape((-1,) + out.shape[2:])
            g = jax.lax.all_gather(v, axis_name)          # (p, gsize*blk, ...)
            mine = jnp.take(jnp.asarray(members), me, axis=0)
            r = jnp.take(jnp.asarray(rank), me)
            sel = jnp.take(g, mine, axis=0)               # (gsize, gsize*blk, ...)
            out = jax.lax.dynamic_slice_in_dim(sel, r * blk, blk, axis=1)
            return out.reshape((-1,) + out.shape[2:])     # (gsize*blk, ...)

        return jax.tree.map(one, x)

    def alltoall_stream(self, x, axis_name, fold, init, gsize,
                        axis_index_groups=None):
        # Always the chunked ring (the very scan the grouped all_to_all
        # uses for large leaves) — streaming is the point, so no one-shot
        # gather fallback regardless of payload size.
        return self._stream_ring(x, axis_name, fold, init, gsize,
                                 axis_index_groups=axis_index_groups)


# ---------------------------------------------------------------------------
# Nested-axis view: one virtual flat axis over an (outer, inner) axis pair
# ---------------------------------------------------------------------------


class NestedCollectives(Collectives):
    """View an ``(outer, inner)`` pair of named mesh axes as one flat axis.

    The sorting algorithms are written against a single named axis of size
    ``p`` (the PR-3 topology contract).  On a hierarchical mesh — e.g.
    inter-host × intra-host, the structure the multi-level scheme of
    arXiv 1410.6754 maps AMS levels onto — the ``p`` participants are laid
    out over *two* named axes ``axes = ((outer, p_o), (inner, p_i))`` with
    flat index ``outer·p_i + inner``.  This view accepts the algorithms'
    collectives on the **virtual** flat axis and decomposes each into
    collectives over the real axes of the wrapped backend:

      * calls naming a real axis pass through unchanged;
      * ``axis_index(virtual)`` composes the per-axis indices;
      * ``ppermute`` permutations must factor through one axis (XOR
        hypercube perms always do: bit ``j`` permutes the inner axis when
        ``j < log2 p_i``, else the outer axis);
      * grouped collectives classify their ``axis_index_groups``: groups
        lying inside one inner slice (with the same pattern in every
        slice, e.g. subcubes of size ≤ p_i) retarget onto the inner axis
        only; groups that are unions of whole outer slices (subcubes of
        size ≥ p_i) decompose into an inner-axis stage plus an outer-axis
        stage.  A full-axis ``all_to_all`` becomes one all_to_all over the
        slow outer axis and one over the inner axis.

    Every decomposition is **element-exact** (same values in the same
    places, not just the same multiset), which is what makes nested runs
    bitwise-identical to the flat ``axis_index_groups`` path.  The wrapped
    backend may be :data:`LAX` (shard_map over a real multi-axis mesh),
    :data:`SIM` (nested vmaps, see :func:`sim_map`'s ``nested=`` mode), or
    a :class:`CountingCollectives` over either — in which case the trace
    records the decomposed launches with their real axis names, splitting
    inter- from intra-axis volume.
    """

    def __init__(self, inner: Collectives, virtual_axis: str,
                 axes: Sequence):
        axes = tuple((str(n), int(s)) for n, s in axes)
        if len(axes) != 2:
            raise NotImplementedError(
                f"NestedCollectives supports exactly 2 nested axes; "
                f"got {axes}")
        self.inner = inner
        self.virtual_axis = virtual_axis
        self.axes = axes
        (self._oa, self._po), (self._ia, self._pi) = axes
        self.p = self._po * self._pi
        self.name = f"nested({inner.name})"

    # -- classification helpers ------------------------------------------

    def _factor_perm(self, perm):
        """Express a flat-axis permutation as a single real-axis ppermute."""
        po, pi = self._po, self._pi
        pairs = [(int(s), int(d)) for s, d in perm]
        srcs = sorted(s for s, _ in pairs)
        dsts = sorted(d for _, d in pairs)
        if srcs == dsts == list(range(self.p)):
            if all(s // pi == d // pi for s, d in pairs):
                maps = [{} for _ in range(po)]
                for s, d in pairs:
                    maps[s // pi][s % pi] = d % pi
                if all(m == maps[0] for m in maps):
                    return self._ia, sorted(maps[0].items())
            if all(s % pi == d % pi for s, d in pairs):
                maps = [{} for _ in range(pi)]
                for s, d in pairs:
                    maps[s % pi][s // pi] = d // pi
                if all(m == maps[0] for m in maps):
                    return self._oa, sorted(maps[0].items())
        raise NotImplementedError(
            f"virtual-axis ppermute does not factor through one of the "
            f"nested axes {self.axes}: {perm}")

    def _classify_groups(self, axis_index_groups):
        """(mode, groups) with mode 'inner' (retarget onto the inner axis)
        or 'outer' (decompose: full inner stage + grouped outer stage).
        ``groups`` are along the real axis; ``None`` = the full axis."""
        po, pi = self._po, self._pi
        if axis_index_groups is None:
            return "outer", None
        groups = [list(map(int, g)) for g in axis_index_groups]
        if _is_full_identity_group(groups) and len(groups[0]) == self.p:
            return "outer", None
        gsize = len(groups[0])
        # groups inside one inner slice, same pattern in every slice
        if gsize <= pi and all(pe // pi == g[0] // pi
                               for g in groups for pe in g):
            per_slice = [[] for _ in range(po)]
            for g in groups:
                per_slice[g[0] // pi].append(tuple(pe % pi for pe in g))
            pattern = sorted(per_slice[0])
            if all(sorted(s) == pattern for s in per_slice):
                inner_groups = [list(g) for g in pattern]
                if _is_full_identity_group(inner_groups) and \
                        len(inner_groups[0]) == pi:
                    return "inner", None
                return "inner", inner_groups
        # groups that are unions of whole outer slices, flat-ascending
        if gsize % pi == 0:
            outer_groups = []
            for g in groups:
                outs = sorted({pe // pi for pe in g})
                if g != [o * pi + i for o in outs for i in range(pi)]:
                    break
                outer_groups.append(outs)
            else:
                if len(outer_groups) == 1 and \
                        outer_groups[0] == list(range(po)):
                    return "outer", None
                return "outer", outer_groups
        raise NotImplementedError(
            f"axis_index_groups do not align with the nested axes "
            f"{self.axes}: {axis_index_groups}")

    # -- the Collectives interface ---------------------------------------

    def axis_index(self, axis_name):
        if axis_name != self.virtual_axis:
            return self.inner.axis_index(axis_name)
        o = self.inner.axis_index(self._oa)
        i = self.inner.axis_index(self._ia)
        return (o * self._pi + i).astype(jnp.int32)

    def ppermute(self, x, axis_name, perm):
        if axis_name != self.virtual_axis:
            return self.inner.ppermute(x, axis_name, perm)
        ax, real_perm = self._factor_perm(perm)
        return self.inner.ppermute(x, ax, real_perm)

    def psum(self, x, axis_name, axis_index_groups=None):
        if axis_name != self.virtual_axis:
            return self.inner.psum(x, axis_name,
                                   axis_index_groups=axis_index_groups)
        mode, g = self._classify_groups(axis_index_groups)
        if mode == "inner":
            return self.inner.psum(x, self._ia, axis_index_groups=g)
        s = self.inner.psum(x, self._ia)
        return self.inner.psum(s, self._oa, axis_index_groups=g)

    def all_gather(self, x, axis_name, axis_index_groups=None, tiled=False):
        if axis_name != self.virtual_axis:
            return self.inner.all_gather(x, axis_name,
                                         axis_index_groups=axis_index_groups,
                                         tiled=tiled)
        mode, g = self._classify_groups(axis_index_groups)
        if mode == "inner":
            return self.inner.all_gather(x, self._ia, axis_index_groups=g,
                                         tiled=tiled)
        gi = self.inner.all_gather(x, self._ia)              # (p_i,) + shape
        go = self.inner.all_gather(gi, self._oa,
                                   axis_index_groups=g)  # (g_o, p_i) + shape

        def flatten(v):
            v = v.reshape((-1,) + v.shape[2:])               # group order
            if tiled:
                v = v.reshape((-1,) + v.shape[2:])
            return v

        return jax.tree.map(flatten, go)

    def all_to_all(self, x, axis_name, split_axis=0, concat_axis=0,
                   axis_index_groups=None, tiled=False):
        if axis_name != self.virtual_axis:
            return self.inner.all_to_all(x, axis_name, split_axis=split_axis,
                                         concat_axis=concat_axis,
                                         axis_index_groups=axis_index_groups,
                                         tiled=tiled)
        mode, g = self._classify_groups(axis_index_groups)
        if mode == "inner":
            return self.inner.all_to_all(x, self._ia, split_axis=split_axis,
                                         concat_axis=concat_axis,
                                         axis_index_groups=g, tiled=tiled)
        if split_axis != 0 or concat_axis != 0 or not tiled:
            raise NotImplementedError(
                "nested virtual all_to_all supports tiled split/concat axis 0")
        pi = self._pi
        g_out = self._po if g is None else len(g[0])
        gsize = g_out * pi

        def one(v):
            assert v.shape[0] % gsize == 0, (v.shape, gsize)
            blk = v.shape[0] // gsize
            # stage 1 — slow axis: chunk jo of the input (p_i·blk rows) is
            # the blocks destined to outer slice jo; after the exchange,
            # y[jo] holds member (jo, my_inner)'s blocks for my slice.
            y = self.inner.all_to_all(v, self._oa, split_axis=0,
                                      concat_axis=0, axis_index_groups=g,
                                      tiled=True)
            y3 = y.reshape((g_out, pi, blk) + v.shape[1:])
            # stage 2 — inner axis: deliver within the slice.  Transposed
            # so the inner a2a splits on axis 0 (both backends support it).
            yt = jnp.moveaxis(y3, 1, 0).reshape((pi * g_out * blk,)
                                                + v.shape[1:])
            z = self.inner.all_to_all(yt, self._ia, split_axis=0,
                                      concat_axis=0, tiled=True)
            z3 = z.reshape((pi, g_out, blk) + v.shape[1:])
            return jnp.moveaxis(z3, 1, 0).reshape((gsize * blk,)
                                                  + v.shape[1:])

        return jax.tree.map(one, x)


@contextlib.contextmanager
def nested(virtual_axis: str, axes, inner: Optional[Collectives] = None):
    """Scope a :class:`NestedCollectives` view over ``inner`` (default: the
    current backend) — the shard_map-side entry point: wrap the *tracing*
    of a body whose collectives name ``virtual_axis`` while the mesh
    carries the real ``axes``.  A surrounding :func:`counting` scope keeps
    counting, now with per-real-axis attribution."""
    base = inner if inner is not None else current()
    with use(NestedCollectives(base, virtual_axis, axes)):
        yield


LAX = LaxCollectives()
SIM = SimCollectives()

# ContextVar, not a module global: tracing may happen from several threads
# (e.g. two jit cache misses racing), and each trace must see its own
# backend scope.
_CURRENT: contextvars.ContextVar[Collectives] = contextvars.ContextVar(
    "repro_collectives", default=LAX)


def current() -> Collectives:
    return _CURRENT.get()


@contextlib.contextmanager
def use(impl: Collectives):
    """Scope the active collectives backend (around *tracing*)."""
    token = _CURRENT.set(impl)
    try:
        yield impl
    finally:
        _CURRENT.reset(token)


# --- module-level dispatchers: the call-site API ---------------------------


def axis_index(axis_name):
    return _CURRENT.get().axis_index(axis_name)


def ppermute(x, axis_name, perm):
    return _CURRENT.get().ppermute(x, axis_name, perm)


def psum(x, axis_name, axis_index_groups=None):
    return _CURRENT.get().psum(x, axis_name,
                               axis_index_groups=axis_index_groups)


def all_gather(x, axis_name, axis_index_groups=None, tiled=False):
    return _CURRENT.get().all_gather(x, axis_name,
                                     axis_index_groups=axis_index_groups,
                                     tiled=tiled)


def all_to_all(x, axis_name, split_axis=0, concat_axis=0,
               axis_index_groups=None, tiled=False):
    return _CURRENT.get().all_to_all(x, axis_name, split_axis=split_axis,
                                     concat_axis=concat_axis,
                                     axis_index_groups=axis_index_groups,
                                     tiled=tiled)


def alltoall_stream(x, axis_name, fold, init, gsize, axis_index_groups=None):
    return _CURRENT.get().alltoall_stream(
        x, axis_name, fold, init, gsize,
        axis_index_groups=axis_index_groups)


# --- simulation runner -----------------------------------------------------


def sim_map(body, axis_name: str, p: Optional[int] = None,
            impl: Optional[Collectives] = None,
            mesh: Optional[Sequence[int]] = None,
            data_axis: Optional[str] = None,
            nested: Optional[Sequence] = None):
    """Run a per-PE SPMD ``body`` over a leading PE axis in one process.

    ``body`` is the same function one would pass to ``shard_map`` minus the
    leading block dimension: inputs/outputs are per-PE values, batched over
    axis 0 of the arguments.  Collectives inside the body must go through
    this module; they dispatch to ``impl`` while the body is traced — pass
    a :class:`CountingCollectives` wrapping :data:`SIM` to record the
    collective trace of a simulated run, or a
    ``SimCollectives(chunk_bytes=...)`` to tune the chunking threshold.

    When ``impl`` is omitted the runner derives a sim-capable backend from
    the *ambient* scope at call time: a surrounding :func:`counting` scope
    keeps counting (re-wrapped over :data:`SIM` with the same trace), an
    ambient ``SimCollectives`` is kept as-is, and anything else (the
    shard_map default) becomes :data:`SIM`.

    **Multi-axis mode** — ``mesh=(d, p)`` emulates a 2-D device mesh
    ``(data_axis, axis_name)``: arguments carry two leading axes ``(d, p,
    ...)`` and the body runs once per (data, sort) coordinate.  Collectives
    inside the body name ``axis_name`` only, so they resolve within each
    row's p-sized sort subgroup — the ``d`` rows never communicate, exactly
    like ``shard_map`` over the sort axis of a 2-D mesh.  Implementation:
    the sort axis is the inner ``vmap(axis_name=...)`` (which gives the
    collectives their named axis) and the data axis an outer ``vmap``
    (named ``data_axis`` if given); vmap's collective batching rules carry
    the sort-axis collectives over the data axis unchanged, so each row is
    bit-identical to a standalone ``sim_map(body, axis_name, p)`` run.

    Sort each row of a batch within its own sort-axis subgroup:

    >>> import jax, jax.numpy as jnp
    >>> from repro.core import comm
    >>> d, p = 2, 4
    >>> def body(v):                       # v: this PE's () block
    ...     lo = comm.all_gather(v, "sort")       # (p,): my subgroup only
    ...     return jnp.sort(lo)[comm.axis_index("sort")]
    >>> x = jnp.array([[3, 1, 0, 2],
    ...                [7, 5, 6, 4]], jnp.int32)
    >>> run = comm.sim_map(body, "sort", p, mesh=(d, p), data_axis="data")
    >>> run(x)
    Array([[0, 1, 2, 3],
           [4, 5, 6, 7]], dtype=int32)

    **Nested-axis mode** — ``nested=(("inter", p_o), ("intra", p_i))``
    emulates a hierarchical mesh: arguments carry one leading axis per
    nested axis (outer first), the body runs once per (outer, inner)
    coordinate under nested ``vmap(axis_name=...)`` transforms, and the
    body's collectives on the *virtual* flat ``axis_name`` are decomposed
    onto the real axes by a :class:`NestedCollectives` view (``impl``, when
    given, becomes the view's wrapped backend).  Bit-identical to the flat
    ``sim_map(body, axis_name, p_o·p_i)`` run of the same body:

    >>> def body2(v):                      # v: this PE's () block
    ...     lo = comm.all_gather(v, "sort", tiled=True)   # all p_o*p_i
    ...     return jnp.sort(lo)[comm.axis_index("sort")]
    >>> y = jnp.array([[3, 1], [0, 2]], jnp.int32)        # (p_o, p_i)
    >>> comm.sim_map(body2, "sort", nested=(("inter", 2), ("intra", 2)))(y)
    Array([[0, 1],
           [2, 3]], dtype=int32)
    """

    def _resolve(cur: Collectives) -> Collectives:
        if isinstance(cur, SimCollectives):
            return cur
        if isinstance(cur, CountingCollectives):
            return CountingCollectives(_resolve(cur.inner), cur.trace)
        if isinstance(cur, FaultyCollectives):
            fc = FaultyCollectives(_resolve(cur.inner), cur.plan, cur.trace)
            # share mutable fault state so the ambient decorator observes
            # what fired inside the sim run
            fc.fired_delays = cur.fired_delays
            fc._launches = cur._launches
            fc._done = cur._done
            return fc
        return SIM

    if nested is not None:
        nested = tuple((str(n), int(s)) for n, s in nested)
        p_nested = 1
        for _, s in nested:
            p_nested *= s
        if p is not None and p != p_nested:
            raise ValueError(f"p={p} inconsistent with nested={nested}")
        p = p_nested

    if mesh is not None:
        d_sz, p_sz = (int(v) for v in mesh)
        if p is not None and p != p_sz:
            raise ValueError(f"p={p} inconsistent with mesh={tuple(mesh)}")
        p = p_sz
    else:
        d_sz = None

    def run(*args):
        axis_lead = tuple(s for _, s in nested) if nested is not None \
            else (p,)
        lead = ((d_sz,) + axis_lead) if d_sz is not None else axis_lead
        if p is not None:
            for a in jax.tree.leaves(args):
                assert a.shape[:len(lead)] == lead, (a.shape, lead)
        backend = impl if impl is not None else _resolve(current())
        if nested is not None:
            backend = NestedCollectives(backend, axis_name, nested)
        with use(backend):
            if nested is not None:
                f = body
                for name, _ in reversed(nested):
                    f = jax.vmap(f, axis_name=name)
            else:
                f = jax.vmap(body, axis_name=axis_name)
            if d_sz is not None:
                f = jax.vmap(f, axis_name=data_axis) if data_axis \
                    else jax.vmap(f)
            return f(*args)

    return run
