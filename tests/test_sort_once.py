"""Each shard is sorted once.

psort's per-PE body hands its algorithm the shard unsorted
(``make_shard(..., sort_local=False)``): every algorithm sorts its own
input, after its shuffle where it has one.  At p = 1 ``rquick`` has no
dimension to exchange along, so it keeps the capacity psort gave the shard
(2·per) and sorts it there, once.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SortConfig, api, comm, psort, types
from repro.core.types import make_shard

ALGORITHMS = ("rquick", "ntb-quick", "rfis", "rams", "ntb-ams", "bitonic",
              "ssort", "ns-ssort", "gatherm", "allgatherm")
# the streamed shuffles and exchanges (overlap=True) of the slotted
# algorithms are separate code paths and take the same unsorted input
CASES = [(a, False) for a in ALGORITHMS] + \
        [(a, True) for a in api._OVERLAP_ALGOS]


@pytest.mark.parametrize("p", [1, 4])
@pytest.mark.parametrize("algorithm,overlap", CASES,
                         ids=[a + ("-overlap" if o else "") for a, o in CASES])
def test_algorithm_sorts_an_unsorted_shard(algorithm, overlap, p):
    per = 256
    n = p * per
    rng = np.random.default_rng(p)
    keys = rng.integers(0, n // 4, n).astype(np.uint32)   # ~4 copies a key
    rng.shuffle(keys)
    kw = {"overlap": True} if overlap else {}

    def body(k, c):
        idx = (comm.axis_index("sort").astype(jnp.uint32) * np.uint32(per)
               + jnp.arange(per, dtype=jnp.uint32))
        shard = make_shard(k, count=c, capacity=2 * per, vals={"idx": idx},
                           sort_local=False)
        out, ovf = api._algorithm_fn(algorithm)(shard, "sort", p, **kw)
        return out.keys, out.vals["idx"], out.count, ovf

    k, i, c, o = jax.jit(comm.sim_map(body, "sort", p))(
        jnp.asarray(keys.reshape(p, per)), jnp.full((p,), per, jnp.int32))
    k, i, c, o = map(np.asarray, (k, i, c, o))
    assert o.sum() == 0
    # allgatherm leaves the whole answer on every PE: read PE 0's
    pes = range(1) if algorithm == "allgatherm" else range(p)
    out = np.concatenate([k[r, :c[r]] for r in pes])
    perm = np.concatenate([i[r, :c[r]] for r in pes])
    np.testing.assert_array_equal(out, np.sort(keys))
    np.testing.assert_array_equal(np.sort(perm), np.arange(n))
    np.testing.assert_array_equal(keys[perm], np.sort(keys))


def _p1_program(monkeypatch, n, kernels):
    """psort's device program for n keys on one device, traced exactly as
    psort called it (the call is stopped before it runs)."""
    monkeypatch.setenv("REPRO_LOCAL_KERNELS", kernels)
    seen = {}

    class Stop(Exception):
        pass

    def spy(*args, **kw):
        seen.update(args=args, kw=kw)
        raise Stop

    real = api._device_program
    monkeypatch.setattr(api, "_device_program", spy)
    keys = np.random.default_rng(n).integers(0, 2**32, n, dtype=np.uint64)
    with pytest.raises(Stop):
        psort(keys.astype(np.uint32),
              config=SortConfig(mesh=api.default_mesh(1)))
    assert seen["kw"]["plan"].algorithm == "rquick"   # psort's pick at p = 1
    return real.trace(*seen["args"], **seen["kw"])


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_p1_program_sorts_once_at_psort_capacity(monkeypatch):
    n = 4096
    traced = _p1_program(monkeypatch, n, "none")
    sorts = [e for e in _eqns(traced.jaxpr.jaxpr)
             if e.primitive.name == "sort"]
    assert [e.invars[0].aval.shape for e in sorts] == [(2 * n,)]
    text = traced.lower().as_text()
    assert text.count("stablehlo.sort") == 1
    assert f"}}) : (tensor<{2 * n}xui32>" in text


def test_p1_program_launches_one_sort_blocks(monkeypatch):
    n = 1 << 14                     # 2·per = 2^15: two kernel tiles
    traced = _p1_program(monkeypatch, n, "sort")
    eqns = list(_eqns(traced.jaxpr.jaxpr))
    assert not [e for e in eqns if e.primitive.name == "sort"]
    launches = [e for e in eqns if e.params.get("name") == "sort_blocks"]
    assert len(launches) == 1
    assert launches[0].invars[0].aval.shape == (2 * n,)
    call, = [e for e in _eqns(launches[0].params["jaxpr"].jaxpr)
             if e.primitive.name == "pallas_call"]
    gm = call.params["grid_mapping"]
    block = np.prod([getattr(b, "block_size", b)
                     for b in gm.block_mappings[0].block_shape])
    assert gm.grid[0] * block == 2 * n


@pytest.mark.parametrize("n,kernel", [(1 << 14, True), (1 << 22, False)],
                         ids=["kernel-below-cap", "xla-above-cap"])
def test_p1_program_local_sort_at_tpu_default(monkeypatch, n, kernel):
    """Under a TPU backend's default policy the p = 1 program sorts its
    2·n-word shard with the bitonic kernel up to ``KERNEL_SORT_MAX_WORDS``
    and above it with one stable XLA sort that carries the index plane as
    an operand: no gather after it and no kernel launch."""
    assert (2 * n <= types.KERNEL_SORT_MAX_WORDS) == kernel
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        policy = types._default_local_kernels()
    assert policy.sort
    prev = types.set_local_kernels(policy)
    try:
        traced = _p1_program(monkeypatch, n, "auto")
    finally:
        types.set_local_kernels(prev)
    eqns = list(_eqns(traced.jaxpr.jaxpr))
    launches = [e for e in eqns if e.params.get("name") == "sort_blocks"]
    sorts = [e for e in eqns if e.primitive.name == "sort"]
    if kernel:
        assert len(launches) == 1 and not sorts
        return
    assert not launches
    assert not [e for e in eqns if e.primitive.name in ("gather",
                                                        "pallas_call")]
    sort, = sorts
    assert [(v.aval.shape, v.aval.dtype) for v in sort.invars] == \
        [((2 * n,), jnp.uint32)] * 2                  # (keys, idx)
    assert sort.params["num_keys"] == 1 and sort.params["is_stable"]
    text = traced.lower().as_text()
    assert text.count("stablehlo.sort") == 1
    assert (f"}}) : (tensor<{2 * n}xui32>, tensor<{2 * n}xui32>) -> "
            in text)
