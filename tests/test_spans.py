"""psort's profiler spans and the device program's phase names.

``psort`` writes ``jax.profiler.TraceAnnotation`` spans on the calling
thread: ``psort`` around the call and, on the in-core paths, its children
``psort.prepare`` < ``psort.wait`` < ``psort.pull`` < ``psort.assemble``.
The tests trace real calls on the CPU and read the profiler's own file.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SortConfig, api, psort
from repro.core.types import local_kernels

CHILDREN = ("psort.prepare", "psort.wait", "psort.pull", "psort.assemble")
N = 4096


def traced_spans(trace_dir, calls):
    """Run ``calls`` under the profiler; the ``psort`` spans of the thread
    that ran them, as (name, start_ns, end_ns, stats) in start order."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        for call in calls:
            call()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats)) for ev in line.events
                   if ev.name == "psort" or ev.name.startswith("psort.")]
            if evs:
                return sorted(evs, key=lambda ev: (ev[1], -ev[2]))
    return []


def by_call(spans):
    """Each ``psort`` span with the spans that start inside it."""
    calls = []
    for ev in spans:
        if ev[0] == "psort":
            calls.append((ev, []))
        else:
            assert calls and calls[-1][0][1] <= ev[1], ev
            calls[-1][1].append(ev)
    return calls


LAYOUTS = {
    "flat_p1": (lambda: SortConfig(mesh=api.default_mesh(1)), 1, 1),
    "flat_p4": (lambda: SortConfig(mesh=api.default_mesh(4)), 1, 4),
    "batched_d2": (lambda: SortConfig(p=2), 2, 2),
    "nested_2x2": (lambda: SortConfig(mesh_shape=(2, 2)), 1, 4),
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_each_call_has_one_psort_span_split_into_four(layout, tmp_path):
    make_cfg, d, p = LAYOUTS[layout]
    cfg = make_cfg()
    rng = np.random.default_rng(13)
    keys = rng.integers(0, 2**32, (d, N) if d > 1 else N, dtype=np.uint64)
    keys = keys.astype(np.uint32)
    _, info = psort(keys, config=cfg, return_info=True)      # compile
    results = []
    spans = traced_spans(tmp_path, [
        lambda: results.append(psort(keys, config=cfg)),
        lambda: results.append(psort(keys, config=cfg, return_info=True))])
    np.testing.assert_array_equal(np.asarray(results[0]),
                                  np.sort(keys, axis=-1))

    plan = api._plan(keys.shape, cfg)
    assert (plan.algorithm, plan.p) == (info["algorithm"], p)
    assert plan.capacity == 2 * (N // p)      # capacity_factor 2.0
    padded = d * p * plan.out_capacity * 4  # the padded u32 keys (d, p, cap)
    counts = d * p * 4                    # the int32 counts (d, p)
    # without the info: keys and counts; with it also the u32 index plane
    # and the int32 overflow counts
    want_pull = [padded + counts, 2 * (padded + counts)]

    calls = by_call(spans)
    assert len(calls) == 2
    for (parent, kids), pull_bytes in zip(calls, want_pull):
        assert [k[0] for k in kids] == list(CHILDREN)
        _, lo, hi, _ = parent
        ends = [lo] + [t for _, s, e, _ in kids for t in (s, e)] + [hi]
        assert ends == sorted(ends)       # in order, apart, inside psort
        covered = sum(e - s for _, s, e, _ in kids)
        assert covered >= 0.9 * (hi - lo)
        stats = {k[0]: k[3] for k in kids}
        assert stats["psort.pull"]["bytes"] == pull_bytes
        assert stats["psort.assemble"]["bytes"] == d * N * 4


def test_an_external_sort_carries_no_wait_pull_or_assemble(tmp_path):
    from repro.core.external import ExternalPolicy
    keys = np.arange(64, dtype=np.int32)[::-1].copy()
    cfg = SortConfig(p=4, backend="sim", external=ExternalPolicy(budget=4))
    psort(keys, config=cfg)                                    # compile
    out = []
    spans = traced_spans(tmp_path, [lambda: out.append(psort(keys,
                                                             config=cfg))])
    np.testing.assert_array_equal(np.asarray(out[0]), np.sort(keys))
    assert [ev[0] for ev in spans] == ["psort", "psort.prepare"]


def test_the_device_program_names_its_phases():
    # RAMS at p = 4 on the shard_map path: its CommTrace tags and the
    # scoped functions reach the compiled ops' op_name metadata
    cfg = SortConfig(mesh=api.default_mesh(4), algorithm="rams")
    plan = api._plan((4 * 256,), cfg)
    keys = jnp.zeros(plan.lead + (plan.per,), jnp.uint32)
    counts = jnp.full(plan.lead, plan.per, jnp.int32)
    text = api._device_program.lower(
        keys, counts, plan=plan,
        pallas=local_kernels()).compile().as_text()
    scopes = {part for name in re.findall(r'op_name="([^"]*)"', text)
              for part in name.split("/")}
    for scope in ("shuffle", "level0", "alltoall_route", "local_sort",
                  "partition_buckets"):
        assert scope in scopes, scope
