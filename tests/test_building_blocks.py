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


def _route_dests(case, p, rng):
    """(p, C) per-PE destinations in [0, p]; p marks an invalid element."""
    if case == "ragged":                       # C not a multiple of p
        return rng.integers(0, p + 1, size=(p, 8 * p + 3))
    C = 8 * p
    if case == "random":
        return rng.integers(0, p + 1, size=(p, C))
    if case == "one_bucket":                   # every element to one PE
        return np.broadcast_to((np.arange(p) * 3 % p)[:, None], (p, C)).copy()
    if case == "all_invalid":
        return np.full((p, C), p)
    if case == "empty_buckets":                # odd PEs receive nothing
        return rng.choice(np.append(np.arange(0, p, 2), p), size=(p, C))
    raise ValueError(case)


def _route_reference(keys, idx, dest, p, slot_cap, pad):
    """The route's exact output layout, from NumPy's per-element ranks."""
    C = dest.shape[1]
    bufk = np.full((p, p, slot_cap), pad, keys.dtype)     # [receiver, source]
    bufi = np.zeros((p, p, slot_cap), idx.dtype)
    counts = np.zeros((p, p), np.int64)
    overflow = np.zeros(p, np.int64)
    for s in range(p):
        order = np.argsort(dest[s], kind="stable")
        sd = dest[s][order]
        rank = np.arange(C) - np.searchsorted(sd, sd, "left")
        slot = np.empty(C, np.int64)
        slot[order] = rank
        sent = np.bincount(dest[s], minlength=p + 1)[:p]
        overflow[s] = np.maximum(sent - slot_cap, 0).sum()
        counts[:, s] = np.minimum(sent, slot_cap)
        ok = (dest[s] < p) & (slot < slot_cap)
        bufk[dest[s][ok], s, slot[ok]] = keys[s][ok]
        bufi[dest[s][ok], s, slot[ok]] = idx[s][ok]
    out_k = np.full((p, p * slot_cap), pad, keys.dtype)
    out_i = np.zeros((p, p * slot_cap), idx.dtype)
    for r in range(p):
        ks = np.concatenate([bufk[r, s, :counts[r, s]] for s in range(p)])
        vs = np.concatenate([bufi[r, s, :counts[r, s]] for s in range(p)])
        out_k[r, :ks.size], out_i[r, :vs.size] = ks, vs
    return out_k, out_i, counts.sum(1), overflow


@pytest.mark.parametrize("stream", [False, True], ids=["barrier", "stream"])
@pytest.mark.parametrize("case", ["random", "one_bucket", "all_invalid",
                                  "empty_buckets", "ragged"])
@pytest.mark.parametrize("p", [1, 2, 4, 8])
def test_alltoall_route_slots_match_reference(p, case, stream):
    """Slot assignment, drops and overflow equal a per-element search."""
    rng = np.random.default_rng(100 * p + len(case))
    dest = _route_dests(case, p, rng).astype(np.int32)
    C = dest.shape[1]
    keys = rng.permutation(p * C).astype(np.uint32).reshape(p, C)
    idx = np.arange(p * C, dtype=np.int32).reshape(p, C)
    slot_cap = C // p + 2            # small enough for one bucket to overflow

    def body(k, i, d):
        sh = ct.make_shard(k, capacity=C, vals={"i": i}, sort_local=False)
        out, ovf = hc._alltoall_route(sh, d, "sort", p, slot_cap,
                                      stream=stream)
        return out.keys, out.vals["i"], out.count[None], ovf[None]

    ks, vs, cnt, ovf = (np.asarray(a) for a in _run(
        body, keys.ravel(), idx.ravel(), dest.ravel(), p=p,
        out_specs=(P("sort"),) * 4))
    ref_k, ref_i, ref_cnt, ref_ovf = _route_reference(
        keys, idx, dest, p, slot_cap, np.iinfo(np.uint32).max)
    if stream:                       # the streamed route arrives sorted
        for r in range(p):
            o = np.argsort(ref_k[r], kind="stable")
            ref_k[r], ref_i[r] = ref_k[r][o], ref_i[r][o]
    np.testing.assert_array_equal(cnt, ref_cnt)
    np.testing.assert_array_equal(ovf, ref_ovf)
    np.testing.assert_array_equal(ks.reshape(p, -1), ref_k)
    for r in range(p):               # vals beyond the count are unspecified
        np.testing.assert_array_equal(vs.reshape(p, -1)[r, :ref_cnt[r]],
                                      ref_i[r, :ref_cnt[r]])
    if case == "one_bucket":         # the reference drops the excess
        assert (ref_ovf == max(C - slot_cap, 0)).all()


def test_alltoall_route_has_no_per_element_search():
    """No while loop of the route carries a C-wide query: at most the
    searched table (the bucket bounds take p + 1 queries)."""
    C, p = 2 ** 12, 4

    def body(k, d):
        sh = ct.make_shard(k, capacity=C, sort_local=False)
        out, ovf = hc._alltoall_route(sh, d, "sort", p, C // p + 64)
        return out.keys, ovf[None]

    mesh = _mesh(p)
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("sort"),) * 2,
                              out_specs=(P("sort"),) * 2, check_vma=False))
    txt = f.lower(jax.ShapeDtypeStruct((p * C,), jnp.uint32),
                  jax.ShapeDtypeStruct((p * C,), jnp.int32)).as_text()
    loops = [ln.split(") : ", 1)[1] for ln in txt.splitlines()
             if "stablehlo.while(" in ln]
    assert loops, "the bounds search no longer lowers to a while loop"
    for carry in loops:
        assert carry.count(f"tensor<{C}xi32>") <= 1, carry


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
