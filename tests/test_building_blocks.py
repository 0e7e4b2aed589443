"""Unit tests for the paper's building blocks (§II, §III): hypercube ops,
randomized shuffling, median windows, data distributions, HLO cost parser."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import types as ct
from repro.core import hypercube as hc

PDEV = 8


def _mesh(p=PDEV):
    return Mesh(np.array(jax.devices()[:p]), ("sort",))


def _run(body, *arrays, p=PDEV, out_specs=None):
    mesh = _mesh(p)
    nspec = tuple(P("sort") for _ in arrays)
    with mesh:
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=nspec,
                                     out_specs=out_specs or P("sort"),
                                     check_vma=False))(*arrays)


def test_hc_exchange_is_involution():
    x = np.arange(PDEV, dtype=np.int32).reshape(PDEV, 1)

    def body(blk):
        v = blk[0]
        w = hc.hc_exchange(v, "sort", PDEV, 1)
        return w[None]

    out = np.asarray(_run(body, x)).ravel()
    assert (out == np.arange(PDEV) ^ 2).all()


def test_butterfly_sum_matches_psum():
    x = np.random.default_rng(0).normal(size=(PDEV, 4)).astype(np.float32)

    def body(blk):
        return hc.butterfly_sum(blk[0], "sort", PDEV,
                                range(3))[None]

    out = np.asarray(_run(body, x))
    np.testing.assert_allclose(out, np.broadcast_to(x.sum(0), (PDEV, 4)),
                               rtol=1e-5)


def test_subcube_prefix_sum():
    x = np.arange(PDEV, dtype=np.int64).reshape(PDEV, 1) + 1

    def body(blk):
        pre, tot = hc.subcube_prefix_sum(blk[0, 0], "sort", PDEV, range(3))
        return jnp.stack([pre, tot])[None]

    out = np.asarray(_run(body, x))
    expect_pre = np.cumsum(np.arange(PDEV) + 1) - (np.arange(PDEV) + 1)
    assert (out[:, 0] == expect_pre).all()
    assert (out[:, 1] == (PDEV * (PDEV + 1)) // 2).all()


def test_hypercube_shuffle_preserves_multiset():
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 1000, size=(PDEV, 16)).astype(np.uint32)

    def body(blk):
        sh = ct.make_shard(blk[0], capacity=64, sort_local=False)
        out, ovf = hc.hypercube_shuffle(sh, "sort", PDEV, seed=7)
        return out.keys[None], out.count[None], ovf[None]

    ks, cnt, ovf = _run(body, keys, out_specs=(P("sort"),) * 3)
    ks, cnt = np.asarray(ks), np.asarray(cnt)
    assert int(np.asarray(ovf).sum()) == 0
    got = np.sort(np.concatenate([ks[i, :cnt[i]] for i in range(PDEV)]))
    assert (got == np.sort(keys.ravel())).all()
    # shuffle must actually move data between PEs (w.h.p.)
    assert any(cnt[i] != 16 for i in range(PDEV)) or \
        not all((np.sort(ks[i, :cnt[i]]) == np.sort(keys[i])).all()
                for i in range(PDEV))


def test_alltoall_shuffle_preserves_multiset():
    rng = np.random.default_rng(2)
    keys = rng.integers(0, 1000, size=(PDEV, 32)).astype(np.uint32)

    def body(blk):
        sh = ct.make_shard(blk[0], capacity=32, sort_local=False)
        out, ovf = hc.alltoall_shuffle(sh, "sort", PDEV, seed=3,
                                       slot_cap=16)
        out, o2 = ct.resize(out, 96)
        return out.keys[None], out.count[None], (ovf + o2)[None]

    ks, cnt, ovf = _run(body, keys, out_specs=(P("sort"),) * 3)
    assert int(np.asarray(ovf).sum()) == 0
    ks, cnt = np.asarray(ks), np.asarray(cnt)
    got = np.sort(np.concatenate([ks[i, :cnt[i]] for i in range(PDEV)]))
    assert (got == np.sort(keys.ravel())).all()


def test_distributions_shapes_and_ranges():
    from repro.data.distributions import INSTANCES, generate_instance
    for name in INSTANCES:
        x = generate_instance(name, 8, 128)
        assert x.shape == (128,)
        assert x.min() >= 0 and x.max() < 2 ** 32, name
    assert len(np.unique(generate_instance("DeterDupl", 8, 512))) <= 3
    assert (generate_instance("Zero", 8, 100) == 0).all()


def test_hlo_cost_parser_on_synthetic_module():
    from repro.launch import hlo_cost
    hlo = """
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %d = f32[8,8] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ip, %d)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%zero, %a)
  %w = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  %ar = f32[8,8] all-reduce(%a), replica_groups={}, to_apply=%cond
  ROOT %r = f32[8,8] get-tuple-element(%w), index=1
}
"""
    r = hlo_cost.analyze(hlo)
    # dot: 2*64*8 = 1024 flops × 10 trips
    assert r["flops"] >= 10 * 1024
    assert r["flops"] < 10 * 1024 + 500
    assert r["collective_bytes"]["all-reduce"] == 2 * 256
    assert r["unknown_trip_counts"] == 0


def test_selection_regime_structure():
    """The paper's headline: regimes ordered GatherM→RFIS→RQuick→RAMS."""
    from repro.core.selection import regime_table
    rows = regime_table(262144)
    order = []
    for _, _, a in rows:
        if not order or order[-1] != a:
            order.append(a)
    assert order == ["gatherm", "rfis", "rquick", "rams"], order


def test_length_balanced_batching_reduces_waste():
    from repro.data.pipeline import length_balanced_batches
    rng = np.random.default_rng(3)
    lengths = np.minimum(32 + (rng.zipf(1.5, size=1024) % 992), 1024)
    _, before, after = length_balanced_batches(lengths, batch=16, p=4)
    assert after < before
