"""psort's plan: one settlement of layout, algorithm and capacities.

``api._plan(keys.shape, cfg)`` is what ``psort``, each fault-lane attempt
and ``trace_collectives`` work from.  These tests hold psort to the plan
it dispatches, and the info dict to one shape across the in-core, fault
and external paths.
"""
import numpy as np
import pytest

from repro.core import SortConfig, api, psort
from repro.core.api import trace_collectives
from repro.core.external import ExternalPolicy
from repro.runtime.failures import FaultPolicy

N, PER = 512, 128                       # p = 4 in every layout
LAYOUTS = {
    # name: (d, config fields, lead, names)
    "flat": (1, dict(p=4), (4,), ("sort",)),
    "batched": (2, dict(p=4), (2, 4), ("data", "sort")),
    "nested": (1, dict(mesh_shape=(2, 2)), (2, 2), ("inter", "intra")),
    "nested_batched": (2, dict(mesh_shape=(2, 2)), (2, 2, 2),
                       ("data", "inter", "intra")),
}


def _info_equal(a, b):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


@pytest.mark.parametrize("backend", ["shard_map", "sim"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_psort_runs_its_plan(layout, backend, monkeypatch):
    d, fields, lead, names = LAYOUTS[layout]
    cfg = SortConfig(backend=backend, **fields)
    keys = np.random.default_rng(17).integers(
        0, 2**32, (d, N) if d > 1 else N, dtype=np.uint64).astype(np.uint32)

    plan = api._plan(keys.shape, cfg)
    assert (plan.lead, plan.names) == (lead, names)
    assert (plan.p, plan.per, plan.capacity) == (4, PER, 2 * PER)
    concentrated = plan.algorithm in ("gatherm", "allgatherm")
    assert plan.out_capacity == (4 * PER if concentrated else 2 * PER)

    seen = []
    real = api._device_program

    def spy(keys_nd, counts_nd, **kw):
        outs = real(keys_nd, counts_nd, **kw)
        seen.append((kw["plan"], keys_nd.shape, counts_nd.shape,
                     outs[0].shape))
        return outs

    monkeypatch.setattr(api, "_device_program", spy)
    out, info = psort(keys, config=cfg, return_info=True)
    np.testing.assert_array_equal(np.asarray(out), np.sort(keys, axis=-1))
    (ran, keys_shape, counts_shape, out_shape), = seen
    assert ran == plan
    assert keys_shape == lead + (PER,) and counts_shape == lead
    assert out_shape == lead + (plan.out_capacity,)
    assert info["algorithm"] == plan.algorithm
    assert (info["backend"], info["mesh_shape"]) == (backend,
                                                     cfg.mesh_shape)
    assert (info["n"], info["d"], info["overflow"]) == (N, d, 0)

    if backend != "sim":
        return
    # the fault lane with nothing to fire, and an external budget the
    # shards fit, return psort's info and only add their own keys
    pol = FaultPolicy()
    _, faulty = psort(keys, config=cfg.replace(fault_policy=pol),
                      return_info=True)
    assert [a["algorithm"] for a in pol.attempts] == [plan.algorithm]
    assert faulty.pop("fault")["p_final"] == 4
    faulty.pop("comm_trace")
    _info_equal(faulty, info)
    if layout == "flat":
        _, fits = psort(keys, config=cfg.replace(
            external=ExternalPolicy(budget=PER)), return_info=True)
        _info_equal(fits, info)


def test_trace_collectives_refuses_levels_outside_the_ams_family():
    cfg = SortConfig(p=4, algorithm="rquick", levels=2)
    with pytest.raises(ValueError, match="levels= applies"):
        psort(np.arange(64, dtype=np.uint32), config=cfg.replace(
            backend="sim"))
    with pytest.raises(ValueError, match="levels= applies"):
        trace_collectives(64, cfg)


@pytest.mark.parametrize("d", [1, 2])
def test_trace_collectives_names_the_configured_axes(d):
    cfg = SortConfig(p=4, algorithm="rams", axis="pe", data_axis="rows")
    trace = trace_collectives(N, cfg, d=d)
    assert trace.counts()["all_to_all"] > 0
    assert set(trace.by_axis()) == {"pe"}
