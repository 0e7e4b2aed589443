"""Collectives-runtime contract tests.

1. Grouped-collective edge cases: ``SimCollectives`` (both the one-shot
   gather path and the forced-ring chunked path) must match
   ``LaxCollectives`` under shard_map at p = 8 — including single-member
   groups, non-contiguous groups and ``tiled=True`` all_gather.
2. ``CountingCollectives``: forwards results unchanged and records the
   per-primitive launch counts / payload bytes / group sizes that
   ``benchmarks/calibrate.py`` fits the machine profile against.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import comm
from repro.core.api import SortConfig, _plan, _sim_runner, trace_collectives

PP = 8
CONTIG = [[0, 1, 2, 3], [4, 5, 6, 7]]
STRIDED = [[0, 2, 4, 6], [1, 3, 5, 7]]          # non-contiguous
SINGLES = [[i] for i in range(PP)]              # single-member groups
FULL = [list(range(PP))]                        # one group == the axis
GROUPS = {"contig": CONTIG, "strided": STRIDED, "singles": SINGLES,
          "full": FULL}


def _run_sim(fn, x, chunk_bytes=None):
    impl = comm.SimCollectives(chunk_bytes=chunk_bytes) \
        if chunk_bytes is not None else None
    return jax.jit(comm.sim_map(fn, "pe", PP, impl=impl))(x)


def _run_shard_map(fn, x):
    mesh = Mesh(np.array(jax.devices()[:PP]), ("pe",))

    def blk(v):
        out = fn(v[0])
        return jax.tree.map(lambda a: a[None], out)

    with mesh:
        return jax.jit(jax.shard_map(blk, mesh=mesh, in_specs=(P("pe"),),
                                     out_specs=P("pe"), check_vma=False))(x)


def _check_all_backends(fn, x):
    """lax reference vs sim one-shot vs sim forced-ring (chunk_bytes=0)."""
    ref = np.asarray(_run_shard_map(fn, x))
    one_shot = np.asarray(_run_sim(fn, x))
    ring = np.asarray(_run_sim(fn, x, chunk_bytes=0))
    np.testing.assert_array_equal(ref, one_shot)
    np.testing.assert_array_equal(ref, ring)


@pytest.mark.parametrize("gname", sorted(GROUPS))
@pytest.mark.parametrize("tiled", [False, True])
def test_grouped_all_gather_matches_lax(gname, tiled):
    groups = GROUPS[gname]
    x = jnp.arange(PP * 3, dtype=jnp.int32).reshape(PP, 3)

    def fn(v):
        return comm.all_gather(v, "pe", axis_index_groups=groups, tiled=tiled)

    _check_all_backends(fn, x)


@pytest.mark.parametrize("gname", sorted(GROUPS))
def test_grouped_psum_matches_lax(gname):
    groups = GROUPS[gname]
    x = (jnp.arange(PP * 4, dtype=jnp.int32).reshape(PP, 4) * 7 + 3)

    def fn(v):
        return comm.psum(v, "pe", axis_index_groups=groups)

    _check_all_backends(fn, x)


@pytest.mark.parametrize("gname", sorted(GROUPS))
def test_grouped_all_to_all_matches_lax(gname):
    groups = GROUPS[gname]
    gsize = len(groups[0])
    blk = 2
    x = jnp.arange(PP * gsize * blk, dtype=jnp.int32).reshape(PP, gsize * blk)

    def fn(v):
        return comm.all_to_all(v, "pe", split_axis=0, concat_axis=0,
                               axis_index_groups=groups, tiled=True)

    _check_all_backends(fn, x)


def test_ungrouped_all_gather_tiled_matches_lax():
    x = jnp.arange(PP * 2, dtype=jnp.int32).reshape(PP, 2)

    def fn(v):
        return comm.all_gather(v, "pe", tiled=True)

    _check_all_backends(fn, x)


def test_rams_forced_ring_bitwise_equal():
    """A full two-level RAMS sort under the forced-ring chunked collectives
    must be bit-identical to the one-shot sim path."""
    p, per = 8, 16
    plan = _plan((p * per,), SortConfig(p=p, algorithm="rams", backend="sim",
                                        levels=2))
    assert plan.capacity == 2 * per and plan.algo_kw == (("levels", 2),)
    r = np.random.default_rng(0)
    keys2d = jnp.asarray(r.integers(0, 2**32, size=(p, per), dtype=np.uint64)
                         .astype(np.uint32))
    counts = jnp.full((p,), per, jnp.int32)
    default = jax.jit(_sim_runner(plan))(keys2d, counts)
    forced = jax.jit(_sim_runner(
        plan, impl=comm.SimCollectives(chunk_bytes=0)))(keys2d, counts)
    for a, b in zip(jax.tree.leaves(default), jax.tree.leaves(forced)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# CountingCollectives
# ---------------------------------------------------------------------------


def test_counting_records_and_forwards():
    x = jnp.arange(PP * 4, dtype=jnp.int32).reshape(PP, 4)
    perm = [(i, (i + 1) % PP) for i in range(PP)]

    def fn(v):
        a = comm.ppermute(v, "pe", perm)
        b = comm.ppermute(a, "pe", perm)
        g = comm.all_gather(v, "pe", axis_index_groups=CONTIG, tiled=True)
        s = comm.psum(v[0], "pe")
        return b + jnp.sum(g).astype(v.dtype) + s

    counter = comm.CountingCollectives(comm.SIM)
    out = jax.jit(comm.sim_map(fn, "pe", PP, impl=counter))(x)
    plain = jax.jit(comm.sim_map(fn, "pe", PP))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))

    tr = counter.trace
    assert tr.counts() == {"ppermute": 2, "all_gather": 1, "psum": 1}
    assert tr.p2p_launches == 2 and tr.fused_launches == 2
    # payload bytes are per-PE and static: 4 int32 per ppermute, 4 for the
    # grouped gather input, 1 scalar for the psum
    assert tr.payload_bytes() == {"ppermute": 32, "all_gather": 16, "psum": 4}
    assert tr.wire_bytes() == 52
    # group sizes: the gather was grouped (4), the psum full-axis (None)
    gathers = [e for e in tr.events if e.primitive == "all_gather"]
    assert gathers[0].group_size == 4
    psums = [e for e in tr.events if e.primitive == "psum"]
    assert psums[0].group_size is None
    assert tr.fused_hops(PP) == pytest.approx(4 ** (1 / 3) + 8 ** (1 / 3))


def test_counting_context_manager_wraps_current():
    with comm.counting() as tr:
        # tracing only — eval_shape never executes FLOPs
        def fn(v):
            return comm.ppermute(v, "pe", [(i, i) for i in range(PP)])
        jax.eval_shape(comm.sim_map(fn, "pe", PP, impl=comm.current()),
                       jax.ShapeDtypeStruct((PP, 2), jnp.float32))
    assert tr.counts() == {"ppermute": 1}
    assert tr.payload_bytes()["ppermute"] == 8


def test_counting_scope_survives_sim_map():
    """The ROADMAP workflow `with comm.counting(): psort(backend='sim')`
    must record the simulated collectives — sim_map re-wraps its backend
    with the ambient counting trace instead of discarding the scope."""
    from repro.core.api import psort
    x = np.random.default_rng(9).integers(0, 1000, 97).astype(np.int32)
    with comm.counting() as tr:
        out = psort(x, config=SortConfig(p=PP, algorithm="rquick",
                                         backend="sim"))
    assert (np.asarray(out) == np.sort(x)).all()
    assert tr.launches > 0 and tr.counts()["ppermute"] > 0


def test_trace_collectives_shapes_of_table1():
    """The counted traces reproduce Table I's structure: hypercube
    algorithms are all point-to-point; RAMS launches fused collectives."""
    t_rquick = trace_collectives(64 * PP, SortConfig(p=PP, algorithm="rquick"))
    assert t_rquick.p2p_launches > 0 and t_rquick.fused_launches == 0
    t_rams = trace_collectives(64 * PP, SortConfig(p=PP, algorithm="rams"))
    assert t_rams.fused_launches > 0
    assert t_rams.wire_bytes() > 0
    # gatherm: d = log2 p exchange steps of the binomial tree
    t_g = trace_collectives(PP // 2, SortConfig(p=PP, algorithm="gatherm"))
    assert t_g.counts()["ppermute"] >= 3
