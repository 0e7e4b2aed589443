"""Differential tests for the fused partition-into-buckets primitive.

Three implementations must agree bitwise everywhere:

  * the pre-existing O(n·nb) one-hot formulation (kept here as a numpy
    oracle — it's what ``rams._rams_level`` shipped before the rewrite);
  * ``partition_ref`` — the jnp reference the sim backend runs;
  * the Pallas kernel (interpret mode on CPU) behind ``partition_buckets``.

Plus the structural guarantee the rewrite exists for: no O(n·nb)
intermediate is materialized anywhere in a traced RAMS level.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core  # noqa: F401  — flips jax_enable_x64 on
from repro.core.types import (LocalKernelPolicy, local_kernels,
                              set_local_kernels, set_pallas_local_sort)
from repro.data.distributions import INSTANCES, generate_instance
from repro.kernels.partition import partition_buckets, partition_ref

AXIS = "pe"


@pytest.fixture
def clean_policy(monkeypatch):
    """No env vars, no programmatic overrides — restores both on exit."""
    monkeypatch.delenv("REPRO_LOCAL_KERNELS", raising=False)
    monkeypatch.delenv("REPRO_PALLAS_LOCAL_SORT", raising=False)
    prev_pol = set_local_kernels(None)
    prev_sort = set_pallas_local_sort(None)
    yield
    set_local_kernels(prev_pol)
    set_pallas_local_sort(prev_sort)


# ---------------------------------------------------------------------------
# the pre-existing path, as a numpy oracle
# ---------------------------------------------------------------------------

def onehot_oracle(keys, ties, s_keys, s_ties, *, n_buckets, count,
                  inclusive=True):
    """O(n·nb) one-hot classify/rank/histogram — the formulation the fused
    primitive replaced (rams._rams_level pre-rewrite, kernels/kway ref).
    ``keys`` / ``s_keys`` are one u32 plane or a tuple of planes (most
    significant first); with the tie plane last they compare
    lexicographically."""
    def planes(k, t):
        k = k if isinstance(k, tuple) else (k,)
        return [x.astype(np.int64) for x in k + (t,)]
    e = [x[:, None] for x in planes(keys, ties)]
    s = [x[None, :] for x in planes(s_keys, s_ties)]
    cmp = (s[-1] <= e[-1]) if inclusive else (s[-1] < e[-1])
    for sp, ep in zip(s[-2::-1], e[-2::-1]):
        cmp = (sp < ep) | ((sp == ep) & cmp)
    bucket = cmp.sum(axis=1).astype(np.int32)
    C = e[0].shape[0]
    bucket = np.where(np.arange(C) < count, bucket, np.int32(n_buckets))
    onehot = bucket[:, None] == np.arange(n_buckets + 1)[None, :]
    hist = onehot[:, :n_buckets].sum(axis=0).astype(np.int32)
    pos = np.where(onehot, np.cumsum(onehot, axis=0) - 1, 0) \
        .sum(axis=1).astype(np.int32)
    return bucket, pos, hist


def _mix(x):
    x = x.astype(np.uint32)
    x ^= x >> 16
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> 13
    return x


def _case(name, C, n_buckets, count, seed=0, tie=True):
    """A locally-sorted (keys, ties) shard + quantile splitters, all u32."""
    gen = INSTANCES[name]
    raw = gen(3, 8, count, seed=seed).astype(np.uint32)
    keys = np.full(C, 0xFFFFFFFF, np.uint32)
    keys[:count] = np.sort(raw)
    ties = _mix(np.arange(C, dtype=np.uint32)) if tie \
        else np.zeros(C, np.uint32)
    ties[count:] = 0xFFFFFFFF
    rng = np.random.default_rng(seed + 1)
    samp = rng.choice(raw, size=max(count, 1), replace=True) if count else \
        np.zeros(1, np.uint32)
    s_keys = np.sort(samp)[
        np.minimum(np.arange(1, n_buckets) * len(samp) // n_buckets,
                   len(samp) - 1)].astype(np.uint32)
    s_ties = _mix(np.arange(n_buckets - 1, dtype=np.uint32)) if tie \
        else np.zeros(n_buckets - 1, np.uint32)
    # splitter composites must be nondecreasing under (key, tie) lex order
    comp = (s_keys.astype(np.uint64) << np.uint64(32)) | s_ties
    order = np.argsort(comp, kind="stable")
    return keys, ties, s_keys[order], s_ties[order]


REF_CASES = [
    ("Uniform", 1024, 64, 1024), ("Uniform", 1000, 8, 777),
    ("Zero", 1024, 64, 1024), ("Zero", 257, 16, 200),
    ("DeterDupl", 512, 32, 512), ("RandDupl", 384, 64, 300),
    ("Staggered", 2048, 128, 2048), ("Mirrored", 192, 2, 100),
    ("Uniform", 256, 16, 0), ("Reverse", 130, 4, 130),
]


@pytest.mark.parametrize("name,C,nb,count", REF_CASES)
@pytest.mark.parametrize("inclusive", [True, False])
def test_partition_ref_matches_onehot_oracle(name, C, nb, count, inclusive):
    keys, ties, sk, st = _case(name, C, nb, count)
    ob, op, oh = onehot_oracle(keys, ties, sk, st, n_buckets=nb, count=count,
                               inclusive=inclusive)
    rb, rp, rh = jax.jit(
        lambda *a: partition_ref(*a, n_buckets=nb, count=count,
                                 inclusive=inclusive)
    )(keys, ties, sk, st)
    np.testing.assert_array_equal(np.asarray(rb), ob)
    np.testing.assert_array_equal(np.asarray(rh), oh)
    assert int(rh.sum()) == count
    # ranks: the oracle gives invalid elements rank 0 (they are in no real
    # bucket); the fused primitive ranks them inside the trash bucket —
    # compare valid entries, and check trash ranks are the stable 0..n-1
    np.testing.assert_array_equal(np.asarray(rp)[:count], op[:count])
    np.testing.assert_array_equal(np.asarray(rp)[count:],
                                  np.arange(C - count, dtype=np.int32))


def test_partition_ref_no_tie_plane():
    keys, ties, sk, st = _case("DeterDupl", 512, 32, 512, tie=False)
    ob, op, oh = onehot_oracle(keys, ties, sk, st, n_buckets=32, count=512)
    rb, rp, rh = partition_ref(keys, ties, sk, st, n_buckets=32, count=512)
    np.testing.assert_array_equal(np.asarray(rb), ob)
    np.testing.assert_array_equal(np.asarray(rp), op)
    np.testing.assert_array_equal(np.asarray(rh), oh)


def test_partition_ref_want_pos_false():
    keys, ties, sk, st = _case("Uniform", 512, 16, 400)
    b1, p1, h1 = partition_ref(keys, ties, sk, st, n_buckets=16, count=400)
    b2, p2, h2 = partition_ref(keys, ties, sk, st, n_buckets=16, count=400,
                               want_pos=False)
    assert p2 is None
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


# ---------------------------------------------------------------------------
# the Pallas kernel (interpret mode) vs the jnp reference
# ---------------------------------------------------------------------------

# nb sweeps the SSSS fan-outs (2 = rquick's split, 128 = deep RAMS level);
# C covers tile-multiple, non-multiple-of-128 and non-pow2 capacities.
KERNEL_CASES = [
    ("Uniform", 1024, 64, 1024), ("Uniform", 1000, 8, 777),
    ("Zero", 1024, 64, 1024), ("Zero", 257, 16, 200),
    ("DeterDupl", 512, 32, 512), ("RandDupl", 384, 128, 300),
    ("Staggered", 4096, 128, 4096), ("Mirrored", 192, 2, 100),
    ("Uniform", 256, 16, 0), ("Reverse", 130, 2, 130),
    ("g-Group", 8256, 64, 8000),
]


@pytest.mark.parametrize("name,C,nb,count", KERNEL_CASES)
@pytest.mark.parametrize("inclusive", [True, False])
def test_partition_kernel_matches_ref(name, C, nb, count, inclusive):
    keys, ties, sk, st = _case(name, C, nb, count)
    args = tuple(map(jnp.asarray, (keys, ties, sk, st)))
    kb, kp, kh = partition_buckets(*args, n_buckets=nb, count=count,
                                   inclusive=inclusive, use_kernel=True)
    rb, rp, rh = partition_buckets(*args, n_buckets=nb, count=count,
                                   inclusive=inclusive, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(kb), np.asarray(rb))
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(rp))
    np.testing.assert_array_equal(np.asarray(kh), np.asarray(rh))
    assert int(np.asarray(kh).sum()) == count


def test_partition_kernel_vmap_batch():
    """The kernel must survive jax batching (the sim backend vmaps every
    per-PE body): 4 lanes with heterogeneous counts vs per-lane ref."""
    B, C, nb = 4, 512, 16
    counts = np.array([512, 300, 1, 0], np.int32)
    lanes = [_case("RandDupl", C, nb, int(c), seed=i)
             for i, c in enumerate(counts)]
    keys = jnp.asarray(np.stack([l[0] for l in lanes]))
    ties = jnp.asarray(np.stack([l[1] for l in lanes]))
    sk = jnp.asarray(lanes[0][2])
    st = jnp.asarray(lanes[0][3])

    def one(k, t, c):
        return partition_buckets(k, t, sk, st, n_buckets=nb, count=c,
                                 use_kernel=True)

    bb, bp, bh = jax.vmap(one)(keys, ties, jnp.asarray(counts))
    for i in range(B):
        rb, rp, rh = partition_buckets(
            keys[i], ties[i], sk, st, n_buckets=nb, count=int(counts[i]),
            use_kernel=False)
        np.testing.assert_array_equal(np.asarray(bb)[i], np.asarray(rb))
        np.testing.assert_array_equal(np.asarray(bp)[i], np.asarray(rp))
        np.testing.assert_array_equal(np.asarray(bh)[i], np.asarray(rh))


def test_partition_kernel_falls_back_below_lane_width():
    """C < 128 can't tile a VPU row — the wrapper must silently take the
    jnp reference and still be exact."""
    keys, ties, sk, st = _case("Uniform", 64, 8, 50)
    kb, kp, kh = partition_buckets(keys, ties, sk, st, n_buckets=8, count=50,
                                   use_kernel=True)
    rb, rp, rh = partition_buckets(keys, ties, sk, st, n_buckets=8, count=50,
                                   use_kernel=False)
    np.testing.assert_array_equal(np.asarray(kb), np.asarray(rb))
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(rp))
    np.testing.assert_array_equal(np.asarray(kh), np.asarray(rh))


# ---------------------------------------------------------------------------
# three planes: (hi, lo, tie) of 64-bit keys
# ---------------------------------------------------------------------------

def _case3(name, C, n_buckets, count, seed=0, hi_from=None):
    """A locally-sorted shard of 64-bit keys as (hi, lo, tie) planes, and
    splitters drawn from it.  ``hi_from`` names the instance of the high
    word (default: the low word's own instance); duplicate instances make
    keys equal in (hi, lo) so only the tie plane decides."""
    gen = INSTANCES[name]
    lo = gen(3, 8, count, seed=seed).astype(np.uint64)
    hi = INSTANCES[hi_from or name](5, 8, count, seed=seed + 7).astype(
        np.uint64)
    wide = np.full(C, 2**64 - 1, np.uint64)
    wide[:count] = np.sort((hi << np.uint64(32)) | lo)
    e_hi = (wide >> np.uint64(32)).astype(np.uint32)
    e_lo = wide.astype(np.uint32)
    ties = _mix(np.arange(C, dtype=np.uint32))
    ties[count:] = 0xFFFFFFFF
    rng = np.random.default_rng(seed + 1)
    pick = rng.integers(0, max(count, 1), size=n_buckets - 1)
    s_wide = wide[pick] if count else np.zeros(n_buckets - 1, np.uint64)
    s_tie = _mix(np.arange(n_buckets - 1, dtype=np.uint32) * np.uint32(7))
    # nondecreasing under (hi, lo, tie); equal keys keep tie order
    order = np.lexsort((s_tie, s_wide))
    s_wide, s_tie = s_wide[order], s_tie[order]
    return ((e_hi, e_lo), ties,
            ((s_wide >> np.uint64(32)).astype(np.uint32),
             s_wide.astype(np.uint32)), s_tie)


THREE_PLANE_CASES = [
    ("Uniform", 1024, 64, 1024, None), ("Uniform", 1000, 8, 777, None),
    ("Zero", 1024, 64, 1024, None),          # one key: ties alone decide
    ("DeterDupl", 512, 32, 512, None),       # three keys, ties decide
    ("Uniform", 512, 16, 500, "Zero"),       # equal hi, lo decides
    ("Zero", 384, 16, 300, "Uniform"),       # equal lo, hi decides
    ("RandDupl", 257, 128, 200, "DeterDupl"), ("Staggered", 2048, 2, 2048,
                                               None),
    ("Uniform", 256, 16, 0, None),
]


@pytest.mark.parametrize("name,C,nb,count,hi_from", THREE_PLANE_CASES)
@pytest.mark.parametrize("inclusive", [True, False])
def test_partition_ref_three_planes_matches_onehot_oracle(
        name, C, nb, count, hi_from, inclusive):
    keys, ties, sk, st = _case3(name, C, nb, count, hi_from=hi_from)
    ob, op, oh = onehot_oracle(keys, ties, sk, st, n_buckets=nb,
                               count=count, inclusive=inclusive)
    rb, rp, rh = jax.jit(
        lambda *a: partition_ref(*a, n_buckets=nb, count=count,
                                 inclusive=inclusive)
    )(keys, ties, sk, st)
    np.testing.assert_array_equal(np.asarray(rb), ob)
    np.testing.assert_array_equal(np.asarray(rh), oh)
    np.testing.assert_array_equal(np.asarray(rp)[:count], op[:count])


@pytest.mark.parametrize("name,C,nb,count,hi_from", THREE_PLANE_CASES)
@pytest.mark.parametrize("inclusive", [True, False])
def test_partition_kernel_three_planes_matches_ref(name, C, nb, count,
                                                   hi_from, inclusive):
    keys, ties, sk, st = _case3(name, C, nb, count, hi_from=hi_from)
    keys, sk = tuple(map(jnp.asarray, keys)), tuple(map(jnp.asarray, sk))
    ties, st = jnp.asarray(ties), jnp.asarray(st)
    kb, kp, kh = partition_buckets(keys, ties, sk, st, n_buckets=nb,
                                   count=count, inclusive=inclusive,
                                   use_kernel=True)
    rb, rp, rh = partition_buckets(keys, ties, sk, st, n_buckets=nb,
                                   count=count, inclusive=inclusive,
                                   use_kernel=False)
    np.testing.assert_array_equal(np.asarray(kb), np.asarray(rb))
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(rp))
    np.testing.assert_array_equal(np.asarray(kh), np.asarray(rh))
    assert int(np.asarray(kh).sum()) == count


def test_three_planes_tie_plane_alone_decides():
    """Every element and splitter share (hi, lo): the bucket is the count
    of splitter ties ≤ the element's tie, on both paths."""
    C, nb = 512, 16
    hi = np.full(C, 0xDEADBEEF, np.uint32)
    lo = np.full(C, 0x12345678, np.uint32)
    ties = _mix(np.arange(C, dtype=np.uint32))
    st = np.sort(_mix(np.arange(nb - 1, dtype=np.uint32) + np.uint32(C)))
    sk = (hi[:nb - 1], lo[:nb - 1])
    want = (st[None, :] <= ties[:, None]).sum(axis=1)
    for use_kernel in (True, False):
        b, _, h = partition_buckets((hi, lo), ties, sk, st, n_buckets=nb,
                                    count=C, use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(b), want)
        assert int(np.asarray(h).sum()) == C


@pytest.mark.parametrize("use_kernel", [True, False])
def test_two_planes_unchanged_by_a_constant_high_plane(use_kernel):
    """A constant high plane in front of (key, tie) changes nothing, and a
    one-plane tuple is the plain two-plane call."""
    keys, ties, sk, st = _case("RandDupl", 1000, 64, 900)
    two = partition_buckets(keys, ties, sk, st, n_buckets=64, count=900,
                            use_kernel=use_kernel)
    one = partition_buckets((keys,), ties, (sk,), st, n_buckets=64,
                            count=900, use_kernel=use_kernel)
    hi = np.full_like(keys, 3)
    three = partition_buckets((hi, keys), ties, (hi[:63], sk), st,
                              n_buckets=64, count=900, use_kernel=use_kernel)
    ob, op, oh = onehot_oracle(keys, ties, sk, st, n_buckets=64, count=900)
    for got in (two, one, three):
        np.testing.assert_array_equal(np.asarray(got[0]), ob)
        np.testing.assert_array_equal(np.asarray(got[1])[:900], op[:900])
        np.testing.assert_array_equal(np.asarray(got[2]), oh)


# ---------------------------------------------------------------------------
# kernel policy: env parsing, overrides, legacy interplay
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec,expect", [
    ("all", (True, True)), ("1", (True, True)), ("on", (True, True)),
    ("", (False, False)), ("0", (False, False)), ("none", (False, False)),
    ("off", (False, False)), ("sort", (True, False)),
    ("partition", (False, True)), ("sort,partition", (True, True)),
    ("partition, sort", (True, True)),
])
def test_local_kernels_env_parsing(clean_policy, monkeypatch, spec, expect):
    monkeypatch.setenv("REPRO_LOCAL_KERNELS", spec)
    pol = local_kernels()
    assert (pol.sort, pol.partition) == expect


def test_local_kernels_env_auto_is_backend_default(clean_policy, monkeypatch):
    monkeypatch.setenv("REPRO_LOCAL_KERNELS", "auto")
    on = jax.default_backend() == "tpu"
    assert local_kernels() == LocalKernelPolicy(sort=on, partition=on)


def test_local_kernels_env_rejects_unknown(clean_policy, monkeypatch):
    monkeypatch.setenv("REPRO_LOCAL_KERNELS", "sort,warp")
    with pytest.raises(ValueError, match="warp"):
        local_kernels()


def test_set_local_kernels_beats_env(clean_policy, monkeypatch):
    monkeypatch.setenv("REPRO_LOCAL_KERNELS", "none")
    prev = set_local_kernels(LocalKernelPolicy(sort=False, partition=True))
    try:
        assert local_kernels() == LocalKernelPolicy(sort=False,
                                                    partition=True)
    finally:
        set_local_kernels(prev)


def test_legacy_sort_flag_layers_onto_policy(clean_policy, monkeypatch):
    # env form: REPRO_PALLAS_LOCAL_SORT only touches the sort component
    monkeypatch.setenv("REPRO_LOCAL_KERNELS", "partition")
    monkeypatch.setenv("REPRO_PALLAS_LOCAL_SORT", "1")
    assert local_kernels() == LocalKernelPolicy(sort=True, partition=True)
    monkeypatch.setenv("REPRO_PALLAS_LOCAL_SORT", "0")
    assert local_kernels() == LocalKernelPolicy(sort=False, partition=True)
    # programmatic form
    monkeypatch.delenv("REPRO_PALLAS_LOCAL_SORT")
    prev = set_pallas_local_sort(True)
    try:
        assert local_kernels().sort is True
    finally:
        set_pallas_local_sort(prev)


# ---------------------------------------------------------------------------
# end to end: psort with kernels on vs off must agree bitwise everywhere
# ---------------------------------------------------------------------------

ALL_ALGOS = ["rquick", "rfis", "rams", "bitonic", "ssort", "gatherm",
             "allgatherm"]
CORE_INSTANCES = ["Uniform", "Zero", "g-Group", "Staggered"]
# instances where classical sample sort legitimately overflows its static
# slots at small p (same subset test_differential.py carves out): there the
# contract is off == on, not overflow == 0.
SSORT_OVERFLOWS = ("Zero", "DeterDupl", "RandDupl", "Mirrored")


def _e2e_cells():
    for algorithm in ALL_ALGOS:
        for instance in sorted(INSTANCES):
            marks = [] if instance in CORE_INSTANCES else [pytest.mark.slow]
            yield pytest.param(algorithm, instance, marks=marks,
                               id=f"{algorithm}-{instance}")


@pytest.mark.parametrize("algorithm,instance", list(_e2e_cells()))
def test_psort_kernel_policy_bitwise(clean_policy, algorithm, instance):
    from repro.core.api import SortConfig, psort
    p = 8
    x = generate_instance(instance, p, 32 * p, seed=3).astype(np.int32)
    set_local_kernels(LocalKernelPolicy())
    cfg = SortConfig(p=p, algorithm=algorithm, backend="sim")
    off, i0 = psort(x, config=cfg, return_info=True)
    set_local_kernels(LocalKernelPolicy(sort=True, partition=True))
    on, i1 = psort(x, config=cfg, return_info=True)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(on))
    assert i0["overflow"] == i1["overflow"]
    if algorithm != "ssort" or instance not in SSORT_OVERFLOWS:
        assert i1["overflow"] == 0
        np.testing.assert_array_equal(np.asarray(on), np.sort(x))


def test_local_kernels_env_busts_psort_jit_cache(clean_policy, monkeypatch):
    """Flipping REPRO_LOCAL_KERNELS between same-signature psort calls must
    retrace (the policy keys the jit cache), not reuse the kernel-less
    executable — and the retraced result must stay bitwise identical."""
    import repro.core.rams as rams_mod
    from repro.core.api import SortConfig, psort
    rng = np.random.default_rng(7)
    x = rng.integers(0, 1 << 20, size=2048).astype(np.int32)

    cfg = SortConfig(p=4, algorithm="rams", backend="sim")
    out_plain = psort(x, config=cfg)

    called = []
    real = rams_mod.partition_buckets
    monkeypatch.setattr(
        rams_mod, "partition_buckets",
        lambda *a, **k: (called.append(1), real(*a, **k))[1])
    monkeypatch.setenv("REPRO_LOCAL_KERNELS", "partition")
    out_kern = psort(x, config=cfg)
    assert called, "policy flip did not retrace psort"
    np.testing.assert_array_equal(np.asarray(out_plain), np.asarray(out_kern))


# ---------------------------------------------------------------------------
# structural: no O(n·nb) intermediate survives in a traced RAMS level
# ---------------------------------------------------------------------------

def _walk_eqns(jaxpr, fn):
    for eqn in jaxpr.eqns:
        fn(eqn)
        for v in eqn.params.values():
            _walk_param(v, fn)


def _walk_param(v, fn):
    if isinstance(v, (tuple, list)):
        for x in v:
            _walk_param(x, fn)
    elif hasattr(v, "eqns"):               # Jaxpr
        _walk_eqns(v, fn)
    elif hasattr(v, "jaxpr"):              # ClosedJaxpr
        _walk_eqns(v.jaxpr, fn)


def test_rams_trace_free_of_onb_intermediates():
    """Trace a full sim-backend RAMS sort at nb=64 and assert the largest
    intermediate stays O(cap) per PE — the old one-hot path materialized
    (2·cap, nb) = 8·16× over this test's threshold."""
    from repro.core import comm
    from repro.core.api import SortConfig, _plan, _sim_runner

    P, PER, CAP = 16, 512, 1024            # levels=1 at p=16 → nb = 4·16 = 64
    plan = _plan((P * PER,), SortConfig(p=P, algorithm="rams", backend="sim",
                                        levels=1, axis=AXIS))
    assert plan.capacity == plan.out_capacity == CAP
    runner = _sim_runner(plan)
    keys2d = jax.ShapeDtypeStruct((P, PER), jnp.uint32)
    counts = jax.ShapeDtypeStruct((P,), jnp.int32)
    jaxpr = jax.make_jaxpr(runner)(keys2d, counts)

    biggest = {"numel": 0, "eqn": None}

    def look(eqn):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", None)
            if shape:
                numel = int(np.prod(shape))
                if numel > biggest["numel"]:
                    biggest["numel"] = numel
                    biggest["eqn"] = str(eqn)[:200]

    _walk_eqns(jaxpr.jaxpr, look)
    # legit peak: the p·slot_cap shuffle buffer ≈ 2.9·cap per PE (×P for the
    # vmapped sim axis). The old one-hot rank was 2·cap·nb = 128·cap per PE.
    limit = P * CAP * 16
    assert biggest["numel"] <= limit, (
        f"O(n·nb)-sized intermediate back in the rams trace: "
        f"{biggest['numel']} > {limit}\n{biggest['eqn']}")
