"""The host wrapper's span metrics: ``psort``'s own spans inside the
harness's span around each call, read from a trace."""
import json
from pathlib import Path

import pytest

from bench import run
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
SPAN_METRICS = {"psort_prepare_ms": "psort.prepare",
                "psort_wait_ms": "psort.wait",
                "psort_pull_ms": "psort.pull",
                "psort_assemble_ms": "psort.assemble"}
CHILDREN = list(SPAN_METRICS.values())


def _view(trace, n=1000, chips=1):
    return tr.View(trace, n=n, chips=chips, peaks={"hbm_bytes_per_s": 1e12})


def test_span_readers_on_a_synthetic_trace():
    # two calls; a psort.wait outside any call (950..990) is not counted
    host = [(tr.WINDOW, 0, 1000),
            (tr.CALL, 10, 400), ("psort", 12, 380),
            ("psort.prepare", 13, 50), ("psort.wait", 50, 300),
            ("psort.pull", 300, 350), ("psort.assemble", 350, 378),
            (tr.HARNESS, 400, 450),
            (tr.CALL, 450, 900), ("psort", 452, 880),
            ("psort.prepare", 453, 470), ("psort.wait", 470, 800),
            ("psort.pull", 800, 860), ("psort.assemble", 860, 879),
            ("psort.wait", 950, 990)]
    trace = tr.Trace((0, 1000), host, {"TPU:0": [("fusion.1", 60, 290)]})
    want = {"psort_prepare_ms": (37 + 17) / 2, "psort_wait_ms": (250 + 330) / 2,
            "psort_pull_ms": (50 + 60) / 2, "psort_assemble_ms": (28 + 19) / 2}
    for name, ns in want.items():
        assert run.reader(name)(_view(trace)) == pytest.approx(ns * 1e-6)


@pytest.mark.parametrize("name", ["v5e1.uniform.lg24.trace.json",
                                  "v5e4.uniform.lg20.trace.json"])
def test_span_readers_find_nothing_in_a_trace_without_spans(name):
    rec = json.loads((DATA / name).read_text())
    view = _view(tr.Trace.from_json(rec["trace"]), rec["n"], rec["chips"])
    for metric in SPAN_METRICS:
        assert run.reader(metric)(view) is None


def test_the_recorded_spans_split_each_psort_call():
    rec = json.loads((DATA / "v5e1.uniform.lg24.spans.trace.json").read_text())
    trace = tr.Trace.from_json(rec["trace"])
    parents = [(s, e) for name, s, e in trace.host if name == "psort"]
    assert len(parents) == len(trace.calls) == rec["calls"]
    for lo, hi in parents:
        kids = sorted((s, name, e) for name, s, e in trace.host
                      if name in CHILDREN and lo <= s < hi)
        assert [name for _, name, _ in kids] == CHILDREN
        ends = [lo] + [t for s, _, e in kids for t in (s, e)] + [hi]
        assert ends == sorted(ends)
        assert sum(e - s for s, _, e in kids) >= 0.95 * (hi - lo)
    assert set(SPAN_METRICS) <= set(rec["metrics"])
