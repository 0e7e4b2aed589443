"""The harness on the CPU: spec, files found by name, inputs, trace
reduction, and a whole run at a small size."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import gen, run
from bench import trace as tr

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------


def test_spec_keys_names_and_units():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert all(PATH.match(p) for p in SPEC["paths"] + SPEC["command"][1:])
    assert 1 <= SPEC["run_seconds"] <= 51
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics] + \
        [w["name"] for w in SPEC["workloads"]] + \
        [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    for name in names + [w["traffic"] for w in SPEC["workloads"]]:
        assert NAME.match(name), name
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in SPEC["per_layer"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
    assert layers <= {"device", "host wrapper", "collective phases",
                      "local kernels"}
    for c in SPEC["configs"]:
        for key in c["reduced"]:
            assert NAME.match(key)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in SPEC["workloads"]:
        cell = run.load_cell(w["name"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:            # each reads a metric it moves
            assert m["moves"] in names


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_files_under_paths_are_named_from_name_characters():
    for p in (ROOT / "bench").rglob("*"):
        rel = p.relative_to(ROOT).as_posix()
        if "__pycache__" in rel or rel.startswith("bench/out"):
            continue
        assert PATH.match(rel), rel


def test_run_seconds_fit_a_check_of_24_cells():
    r = SPEC["run_seconds"]
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------


def _copy_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    return tmp_path


def test_new_config_traffic_and_metric_picked_up_by_name(tmp_path):
    root = _copy_checkout(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    conf = json.loads((root / "bench/configs/v5e1-u32.json").read_text())
    conf["name"] = "v5e1-new"
    (root / "bench/configs/v5e1-new.json").write_text(json.dumps(conf))
    (root / "bench/workloads/deterdupl.lg16.json").write_text(json.dumps({
        "instance": "deter_dupl", "n": 65536, "loop": "closed",
        "callers": 1, "distinct_inputs": 2, "why": "a test mix"}))
    (root / "bench/metrics/calls_traced.py").write_text(
        "def read(view):\n    return float(len(view.calls)) or None\n")
    spec["configs"].append(dict(spec["configs"][0], name="v5e1-new",
                                file="bench/configs/v5e1-new.json"))
    spec["workloads"].append({"name": "v5e1.deterdupl.lg16",
                              "config": "v5e1-new",
                              "traffic": "deterdupl.lg16", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "calls_traced", "unit": "calls",
                              "better": "higher", "source": "device_trace",
                              "layer": "host wrapper", "moves": "keys_per_s",
                              "workloads": ["v5e1.deterdupl.lg16"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = run.load_cell("v5e1.deterdupl.lg16", root)
    assert cell.config["name"] == "v5e1-new"
    assert cell.traffic["instance"] == "deter_dupl" and cell.n == 65536
    assert "calls_traced" in [m["name"] for m in cell.per_layer]
    view = tr.View(tr.Trace((0, 10), [(tr.CALL, 1, 4), (tr.CALL, 5, 9)],
                            {}), n=cell.n, chips=1, peaks={})
    assert run.reader("calls_traced", root)(view) == 2.0


def test_new_instance_and_key_type_picked_up_by_name(tmp_path):
    # a 64-bit configuration and an instance of its own, with no edit to
    # any file of the harness; the whole run is judged correct
    import jax
    root = _copy_checkout(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    conf = json.loads((root / "bench/configs/v5e1-u32.json").read_text())
    conf.update(name="v5e1-u64", key_dtype="uint64")
    (root / "bench/configs/v5e1-u64.json").write_text(json.dumps(conf))
    (root / "bench/instances/top_heavy.py").write_text(
        "from bench.gen import draw, rng\n\n\n"
        "def gen(i, p, m, seed, bits):\n"
        "    return draw(rng(seed, i), 2 ** (bits - 1), 2 ** bits, m, bits)\n")
    (root / "bench/workloads/top_heavy.lg12.json").write_text(json.dumps({
        "instance": "top_heavy", "n": 4096, "loop": "closed",
        "callers": 1, "distinct_inputs": 2, "why": "a test mix"}))
    spec["configs"].append(dict(spec["configs"][0], name="v5e1-u64",
                                file="bench/configs/v5e1-u64.json"))
    spec["workloads"].append({"name": "v5e1u64.top_heavy.lg12",
                              "config": "v5e1-u64",
                              "traffic": "top_heavy.lg12", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = run.load_cell("v5e1u64.top_heavy.lg12", root)
    assert cell.dtype == np.uint64
    keys, = gen.inputs(dict(cell.traffic, distinct_inputs=1), 1, 9,
                       dtype=cell.dtype, root=root)
    assert keys.dtype == np.uint64 and keys.min() >= 2**63
    result, checks = run.run_cell(cell, 2**31 + 9, 0.2, False,
                                  jax.devices()[:1],
                                  {"hbm_bytes_per_s": 819e9})
    assert result["correct"], checks


def test_every_metric_and_traffic_has_its_file():
    for m in SPEC["per_layer"]:
        assert callable(run.reader(m["name"]))
    for w in SPEC["workloads"]:
        path = ROOT / "bench/workloads" / f"{w['traffic']}.json"
        traffic = json.loads(path.read_text())
        assert (ROOT / "bench/instances" /
                f"{traffic['instance']}.py").is_file()
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 3 * 2**31])
def test_same_seed_same_keys(seed):
    traffic = {"instance": "uniform", "n": 4096, "distinct_inputs": 3}
    a, b = gen.inputs(traffic, 4, seed), gen.inputs(traffic, 4, seed)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert all(x.dtype == np.uint32 and x.size == 4096 for x in a)
    assert not np.array_equal(a[0], a[1])
    other = gen.inputs(traffic, 4, seed + 1)
    assert not np.array_equal(a[0], other[0])


def test_generators_keep_their_values():
    # The paper's instances as the benchmark first drew them: the yardstick
    # must not move.
    x = gen.instance("uniform", 4, 1000, seed=5)
    assert x[:3].tolist() == [2730396786, 2383523134, 146049766]
    assert x[-2:].tolist() == [2114999070, 1202748289]
    assert gen.instance("deter_dupl", 4, 1000, seed=5)[:6].tolist() == \
        [1, 1, 0, 1, 0, 0]
    assert gen.instance("all_to_one", 4, 1000, seed=5)[248:251].tolist() == \
        [4294967294, 4, 3541712770]


@pytest.mark.parametrize("name", ["uniform", "deter_dupl", "all_to_one"])
def test_instances_span_the_key_width(name):
    traffic = {"instance": name, "n": 4096, "distinct_inputs": 1}
    for dtype in (np.uint32, np.uint64):
        x, = gen.inputs(traffic, 4, 2**31 + 5, dtype=dtype)
        assert x.dtype == dtype and x.size == 4096
    wide, = gen.inputs(traffic, 4, 2**31 + 5, dtype=np.uint64)
    if name != "deter_dupl":
        assert wide.max() >= 2**32


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def _synthetic():
    # window 0..100; two calls; TPU:0 busy 10..30 and 50..60 (a collective
    # 20..30 with nothing beside it), TPU:1 busy 10..20.
    host = [(tr.WINDOW, 0, 100), (tr.CALL, 5, 40), (tr.HARNESS, 40, 45),
            (tr.CALL, 45, 70), ("PjitFunction(f)", 6, 8)]
    devices = {"TPU:0": [("fusion.1", 10, 20), ("all-to-all.3", 20, 30),
                         ("fusion.1", 50, 60)],
               "TPU:1": [("fusion.1", 10, 20)]}
    return tr.Trace((0, 100), host, devices)


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (9, 9)]) == [(0, 3), (5, 7)]
    assert tr.intersect([(0, 3), (5, 9)], [(2, 6)]) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 6)]) == \
        [(0, 2), (3, 5), (6, 10)]
    assert tr.subtract([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]


def test_collectives_pair_start_with_done():
    ops = [("all-gather-start.1", 0, 1), ("fusion.2", 1, 5),
           ("all-gather-done.1", 5, 6), ("collective-permute.4", 7, 8),
           ("all-reduce-start", 9, 10), ("all-reduce-done", 12, 13)]
    assert tr.collective_intervals(ops) == [(0, 6), (7, 8), (9, 13)]
    assert not tr.is_collective("fusion.all-gather")


def test_readers_on_a_synthetic_trace():
    view = tr.View(_synthetic(), n=1000, chips=2,
                   peaks={"hbm_bytes_per_s": 1e12})
    assert view.busy_s() == pytest.approx((30 + 10) / 2 * 1e-9)
    assert run.reader("device_idle_pct")(view) == pytest.approx(80.0)
    # call 1: 35 ns, busy 10..30 -> 15 ns idle; call 2: 25 ns, 10 busy -> 15
    assert run.reader("host_ms_per_sort")(view) == pytest.approx(15e-6)
    assert run.reader("collective_ms_per_sort")(view) == pytest.approx(5e-6)
    assert run.reader("collective_exposed_pct")(view) == pytest.approx(100.0)
    least = 2 * 4 * 1000 / 2 / 1e12
    assert run.reader("local_sort_roofline")(view) == \
        pytest.approx(100 * least / 15e-9)
    b = tr.breakdown(view)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(20e-9)]
    assert b["idle_gaps"] == [[tr.WINDOW, pytest.approx(40e-9)],
                              [f"{tr.WINDOW} > {tr.HARNESS}",
                               pytest.approx(20e-9)],
                              [f"{tr.WINDOW} > {tr.CALL}",
                               pytest.approx(10e-9)]]


def test_the_harness_programs_ops_are_kept_apart():
    modules = [("jit__psort_jit(11)", 0, 50),
               (f"{tr.HARNESS_PROGRAM}(7)", 60, 70),
               ("jit_bench_digestive(3)", 80, 90)]
    ops = [("fusion.1", 10, 20), ("reduce.2", 60, 65), ("fusion.3", 65, 70),
           ("copy.4", 80, 85)]
    program, harness = tr.split_harness(ops, modules)
    assert harness == [("reduce.2", 60, 65), ("fusion.3", 65, 70)]
    assert program == [("fusion.1", 10, 20), ("copy.4", 80, 85)]
    trace = tr.Trace((0, 100), [(tr.WINDOW, 0, 100), (tr.CALL, 5, 90)],
                     {"TPU:0": program}, {"TPU:0": harness})
    back = tr.Trace.from_json(json.loads(json.dumps(trace.to_json())))
    assert back == trace
    view = tr.View(back, n=1000, chips=1, peaks={})
    assert view.busy_s() == pytest.approx(15e-9)


def test_readers_find_nothing_without_a_device():
    trace = tr.Trace((0, 100), [(tr.WINDOW, 0, 100), (tr.CALL, 5, 40)], {})
    view = tr.View(trace, n=1000, chips=1, peaks={"hbm_bytes_per_s": 1e12})
    for m in SPEC["per_layer"]:
        assert run.reader(m["name"])(view) is None


RECORDED = sorted(DATA.glob("*.trace.json"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_reduction_of_a_trace_recorded_on_the_chip(path):
    rec = json.loads(path.read_text())
    view = tr.View(tr.Trace.from_json(rec["trace"]), n=rec["n"],
                   chips=rec["chips"], peaks=rec["peaks"])
    assert len(view.calls) == rec["calls"]
    for name, want in rec["metrics"].items():
        got = run.reader(name)(view)
        assert got == pytest.approx(want, rel=1e-9), name
        if name.endswith("_pct") or name.endswith("_roofline"):
            assert 0 < got <= 100
    assert view.busy_s() == pytest.approx(rec["busy_s"], rel=1e-9)
    assert 0 < view.busy_s() <= view.window_s


# ---------------------------------------------------------------------------
# whole runs
# ---------------------------------------------------------------------------


def small_cell(name, n=1 << 12):
    cell = run.load_cell(name)
    cell.traffic = dict(cell.traffic, n=n)
    return cell


@pytest.mark.parametrize("name,chips", [("v5e1.uniform.lg24", 1),
                                        ("v5e4.uniform.lg20", 4)])
def test_a_run_at_a_small_size_is_correct(name, chips):
    import jax
    cell = small_cell(name)
    result, checks = run.run_cell(cell, 2**31 + 3, 0.3, False,
                                  jax.devices()[:chips],
                                  {"hbm_bytes_per_s": 819e9})
    assert result["correct"] and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] >= 0 for v in result["metrics"].values())
    assert checks["calls_checked"][0] == result["attempted"] >= run.MIN_CALLS


def test_a_traced_run_reads_nothing_from_a_cpu_trace():
    import jax
    cell = small_cell("v5e1.uniform.lg24")
    result, _ = run.run_cell(cell, 5, 0.2, True, jax.devices()[:1],
                             {"hbm_bytes_per_s": 819e9})
    assert result["correct"] and result["metrics"] == {}
    assert result["device"]["window_s"] > 0
    assert result["device"]["busy_s"] == 0


def _bench(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", "v5e1.uniform.lg24", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_no_tpu_no_result():
    proc = _bench(ARGS, ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_a_checkout_of_only_the_benchmark_gives_no_result(tmp_path):
    root = _copy_checkout(tmp_path)
    proc = _bench(ARGS, root)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
