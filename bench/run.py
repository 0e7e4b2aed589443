"""Chip benchmark of ``psort``: one cell of ``BENCHMARK.json``, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: chips, key
type and ``SortConfig`` fields) and a traffic mix
(``bench/workloads/<traffic>.json``: instance, n, closed loop, distinct
inputs; the instance is ``bench/instances/<instance>.py``).  The run makes
the inputs from ``--seed`` on the host, warms up the cell's one shape, then
calls
``psort(keys, config=...)`` back to back, as a user does, for ``--seconds``
seconds, each call timed to ``block_until_ready`` on its answer.  Every
answer is checked against ``np.sort`` after the window (``bench/check.py``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces a
short window with the JAX profiler and prints the cell's per-layer metrics,
each read by ``bench/metrics/<name>.py`` from the trace (``bench/trace.py``).
The last line of standard output is one JSON object; the numbers compared
for ``correct`` are the last lines of standard error.  Without a TPU, with
fewer chips than the cell asks for, or with a device missing from
``bench/peaks.json`` the run fails and prints no result.

The persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``.  Traces go to ``bench/out/<cell>/``.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
# Import the benchmark's modules as ``bench.*``: with bench/ itself first on
# the path, ``trace`` would shadow the standard library's module.
if sys.path and sys.path[0] and Path(sys.path[0]).resolve() == BENCH:
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import check, gen  # noqa: E402
from bench import trace as tr  # noqa: E402

TRACE_SECONDS = 3.0          # longest traced window
MIN_CALLS = 2                # a window holds at least this many calls
WARM_CALLS = 2               # set-up calls (the first compiles)
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec",
                   "/jax/core/compile/jaxpr_trace_duration")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                 # the configuration's file
    traffic: dict                # the traffic mix's file
    end_to_end: list             # BENCHMARK.json entries this cell reports
    per_layer: list
    root: Path = ROOT            # the checkout the files were read from

    @property
    def n(self) -> int:
        return int(self.traffic["n"])

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self.config["key_dtype"])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(know {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "workloads" / f"{w['traffic']}.json").read_text())
    if int(config["chips"]) != int(w["chips"]):
        raise ValueError(f"{name}: cell asks for {w['chips']} chips, "
                         f"{w['config']} is for {config['chips']}")
    if int(traffic["n"]) > int(config["keys_per_chip"]) * int(w["chips"]):
        raise ValueError(f"{name}: n = {traffic['n']} exceeds what "
                         f"{w['config']} holds")

    def mine(metric):
        return name in metric.get("workloads", [name])

    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)], root)


def reader(metric: str, root: Path = ROOT):
    """The per-layer metric's own reader, ``bench/metrics/<metric>.py``."""
    return gen.module(root / "bench" / "metrics" / f"{metric}.py").read


class CompileCounter:
    """Counts JAX's tracing, compiling and cache-loading events."""

    def __init__(self):
        import jax
        self.count = 0
        self._monitoring = jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in _COMPILE_EVENTS:
            self.count += 1

    def close(self):
        self._monitoring.unregister_event_duration_listener(self._on)


def use_compile_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, devices,
             peaks: dict, sort=None, t0: float = _T0):
    """Set up, run the window, check every answer; returns the result
    object and the checks {name: (value, limit)}.  ``sort`` replaces
    ``psort`` (the control); ``devices`` are the cell's chips."""
    import jax

    from repro.core import SortConfig, psort
    from repro.core.api import default_mesh

    counter = CompileCounter()
    n = cell.n
    inputs = gen.inputs(cell.traffic, p=cell.chips, seed=seed,
                        dtype=cell.dtype, root=cell.root)
    if sort is None:
        cfg = SortConfig(mesh=default_mesh(cell.chips),
                         **cell.config["sort_config"])
        sort = lambda keys: psort(keys, config=cfg)   # noqa: E731
    digest = check.device_digest_fn()

    def well_formed(out):
        return out.shape == (n,) and out.dtype == cell.dtype

    for j in range(WARM_CALLS):
        out = sort(inputs[j % len(inputs)])
        out.block_until_ready()
        if well_formed(out):
            digest(out).block_until_ready()
    del out

    out_dir = BENCH / "out" / cell.name
    window_s = min(seconds, TRACE_SECONDS) if traced else seconds
    if traced:
        shutil.rmtree(out_dir / "profile", ignore_errors=True)
        jax.profiler.start_trace(str(out_dir / "profile"))
    setup_s = time.perf_counter() - t0
    counter.count = 0
    lat, calls, digests = [], [], []
    start = time.perf_counter()
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        while True:
            j = len(calls) % len(inputs)
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(tr.CALL):
                out = sort(inputs[j])
                out.block_until_ready()
            done = time.perf_counter()
            with jax.profiler.TraceAnnotation(tr.HARNESS):
                lat.append(done - t)
                calls.append(j)
                digests.append(digest(out) if well_formed(out) else None)
            if done - start >= window_s and len(calls) >= MIN_CALLS:
                break
    elapsed = time.perf_counter() - start
    in_window = counter.count
    counter.close()
    if traced:
        jax.profiler.stop_trace()
    mem = peak_bytes(devices)
    last = np.asarray(out)
    del out
    digests = [None if d is None else np.asarray(d) for d in digests]
    log(f"{cell.name}: set-up {setup_s:.3f} s; {len(calls)} calls in "
        f"{elapsed:.3f} s; compilations in the window: {in_window}")
    ms = np.asarray(lat) * 1e3
    log(f"{cell.name}: call ms min {ms.min():.2f}, median "
        f"{np.median(ms):.2f}, max {ms.max():.2f}; slowest calls at "
        f"{sorted(np.argsort(ms)[-3:].tolist())}")

    checks = check.judge(inputs, calls, digests, last, calls[-1], n)
    dev0 = devices[0]
    result = {"correct": check.passed(checks), "attempted": len(calls),
              "failed": checks["wrong_calls"][0], "metrics": {},
              "device": {"platform": dev0.platform,
                         "kind": dev0.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": mem}}
    if traced:
        trace = tr.load(tr.xplane_file(str(out_dir / "profile")))
        (out_dir / "trace.json").write_text(json.dumps(trace.to_json()))
        view = tr.View(trace, n=n, chips=cell.chips, peaks=peaks)
        digest_ns = max([tr.length(tr.union([(s, e) for _, s, e in evs]))
                         for evs in trace.harness.values()], default=0)
        log(f"{cell.name}: the digest's device time, left out of the "
            f"per-layer metrics: {digest_ns * 1e-6 / len(calls):.4f} ms a "
            f"call")
        for m in cell.per_layer:
            value = reader(m["name"], cell.root)(view)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = view.busy_s()
        result["device"]["window_s"] = view.window_s
        result["breakdown"] = tr.breakdown(view)
    else:
        e2e = {"keys_per_s": len(calls) * n / elapsed,
               "sort_p90_ms": float(np.percentile(lat, 90)) * 1e3,
               "peak_bytes_per_key": mem / n,
               "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="sort with bench/check.py's control in psort's "
                         "place (it must come out as not correct)")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        log("bench: the program (src/repro) is not in this checkout")
        return 2
    cell = load_cell(args.workload)
    sys.path.insert(1, str(SRC))
    # libtpu would otherwise log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: no TPU (JAX found {devices[0].platform})")
        return 1
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
            f"{len(devices)}")
        return 1
    table = json.loads((BENCH / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in table:
        log(f"bench: device kind {kind!r} is not in bench/peaks.json")
        return 1
    log(f"bench: {kind} x{len(devices)}; compile cache "
        f"{use_compile_cache(jax)}")

    sort = None
    if args.control:
        def sort(keys):
            return jax.device_put(check.control_sort(keys), devices[0])
    result, checks = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices[:cell.chips],
                              table[kind], sort=sort)
    for name, (value, limit) in checks.items():
        rel = ">=" if name == "calls_checked" else "<="
        log(f"check {name} = {value} (limit {rel} {limit})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
