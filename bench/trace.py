"""From a profiler trace of a cell's traced window to intervals and shares.

The harness writes its own spans into the trace with
``jax.profiler.TraceAnnotation``: :data:`WINDOW` around the traced window,
:data:`CALL` around each ``psort`` call (to ``block_until_ready`` on its
answer) and :data:`HARNESS` around its own work between calls.  This
module reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` into a
:class:`Trace`: those spans, the other events on the same host thread, and
the operations on each device's "XLA Ops" line, all in nanoseconds on the
trace's one clock.  The operations of the harness's own programs (the
answer's digest, :data:`HARNESS_PROGRAM` on the "XLA Modules" line) are
kept apart, so that no metric counts them as the program's.  A
:class:`Trace` is also written to and read from JSON, which is how a
recorded trace is kept for the tests.

Per-layer metrics are readers of their own (``bench/metrics/<name>.py``),
each a function ``read(view) -> float | None`` of a :class:`View`.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
CALL = "bench.psort"
HARNESS = "bench.harness"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HARNESS_PROGRAM = "jit_bench_digest"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "collective-broadcast", "ragged-all-to-all")

Event = Tuple[str, int, int]            # (name, start_ns, end_ns)
Interval = Tuple[int, int]
_HLO = re.compile(r"^%?(\S+) = (.*?) ([\w-]+)\(")


def op_label(text: str) -> str:
    """``name opcode shape`` of an HLO instruction as the trace names it
    (``%reverse.44 = u32[1,1,33554432]{2,1,0:T(1,128)} reverse(...)`` ->
    ``reverse.44 reverse u32[1,1,33554432]``); other names unchanged."""
    m = _HLO.match(text)
    if not m:
        return text
    shape = re.sub(r"\{[^{}]*\}", "", m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape}"


@dataclasses.dataclass
class Trace:
    window: Interval
    host: List[Event]                   # the harness thread's events
    devices: Dict[str, List[Event]]     # device name -> its XLA ops
    harness: Dict[str, List[Event]] = dataclasses.field(default_factory=dict)
    # device name -> the ops of the harness's own programs

    @property
    def calls(self) -> List[Interval]:
        return [(s, e) for name, s, e in self.host if name == CALL]

    def to_json(self) -> dict:
        return {"window": list(self.window),
                "host": [list(e) for e in self.host],
                "devices": {d: [list(e) for e in evs]
                            for d, evs in self.devices.items()},
                "harness": {d: [list(e) for e in evs]
                            for d, evs in self.harness.items()}}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        def ops(by_device):
            return {d: [(op_label(e[0]), e[1], e[2]) for e in evs]
                    for d, evs in by_device.items()}
        return cls(tuple(obj["window"]), [tuple(e) for e in obj["host"]],
                   ops(obj["devices"]), ops(obj.get("harness", {})))


def xplane_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def split_harness(ops: Sequence[Event], modules: Sequence[Event]):
    """``ops`` less those that start inside a run of the harness's own
    programs among ``modules``, and those apart."""
    spans = union([(s, e) for name, s, e in modules
                   if name.split("(")[0] == HARNESS_PROGRAM])
    starts = [s for s, _ in spans]
    program, harness = [], []
    for ev in ops:
        k = bisect.bisect_right(starts, ev[1]) - 1
        inside = k >= 0 and ev[1] < spans[k][1]
        (harness if inside else program).append(ev)
    return program, harness


def load(path: str) -> Trace:
    """Read the harness's spans and each TPU's XLA ops from an xplane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, window, ops, modules = None, None, {}, {}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                evs = [(ev.name, int(ev.start_ns), int(ev.end_ns))
                       for ev in line.events]
                into = ops if line.name == OPS_LINE else modules
                into[f"TPU:{m.group(1)}"] = evs
            elif plane.name.startswith("/host:") and host is None:
                evs = [(ev.name, int(ev.start_ns), int(ev.end_ns))
                       for ev in line.events]
                win = [e for e in evs if e[0] == WINDOW]
                if win:
                    host, window = evs, (win[0][1], win[0][2])
    if host is None:
        raise ValueError(f"{path}: no {WINDOW!r} span on any host thread")
    lo, hi = window
    host = [e for e in host if e[2] > lo and e[1] < hi]
    devices, harness = {}, {}
    for d in sorted(ops, key=lambda name: int(name[4:])):
        evs = [(op_label(name), s, e) for name, s, e in ops[d]
               if e > lo and s < hi]
        devices[d], harness[d] = split_harness(evs, modules.get(d, []))
    return Trace(window, host, devices, harness)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged: Sequence[Interval]) -> int:
    return sum(e - s for s, e in merged)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two unions (each sorted and disjoint)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a`` less ``b`` (each sorted and disjoint)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _kind(label: str) -> str:
    """The collective an op label names (``all-to-all-start``), or ''."""
    for word in label.split(" ")[:2]:
        base = word.split(".")[0]
        if any(base == c or base in (c + "-start", c + "-done")
               for c in COLLECTIVES):
            return base
    return ""


def is_collective(label: str) -> bool:
    return bool(_kind(label))


def collective_intervals(ops: Sequence[Event]) -> List[Interval]:
    """Each synchronous collective's own interval, and each asynchronous
    one's from its ``-start`` to the matching ``-done`` (the next ``-done``
    of the same kind)."""
    out, open_ = [], {}
    for name, s, e in sorted(ops, key=lambda ev: ev[1]):
        base = _kind(name)
        if not base:
            continue
        if base.endswith("-start"):
            open_.setdefault(base[:-6], []).append(s)
        elif base.endswith("-done") and open_.get(base[:-5]):
            out.append((open_[base[:-5]].pop(0), e))
        else:
            out.append((s, e))
    return out


# ---------------------------------------------------------------------------
# what a metric reader sees
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class View:
    """A traced window, with what the cell knows about its sorts."""

    trace: Trace
    n: int                       # keys per sort
    chips: int                   # chips the cell uses
    peaks: dict                  # this device kind's entry of peaks.json

    def __post_init__(self):
        self.devices = list(self.trace.devices)[: self.chips]
        lo, hi = self.trace.window
        self.window_ns = hi - lo
        self.calls = self.trace.calls
        self._calls_union = union(self.calls)
        self.busy = {d: intersect(union([(s, e) for _, s, e
                                         in self.trace.devices[d]]),
                                  [self.trace.window])
                     for d in self.devices}

    @property
    def window_s(self) -> float:
        return self.window_ns * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(length(b) for b in self.busy.values()) \
            / len(self.devices) * 1e-9

    def busiest(self) -> Optional[str]:
        if not self.devices:
            return None
        return max(self.devices, key=lambda d: length(self.busy[d]))

    def any_busy(self) -> List[Interval]:
        return union([iv for b in self.busy.values() for iv in b])

    def in_calls(self, merged: Sequence[Interval]) -> List[Interval]:
        return intersect(merged, self._calls_union)

    def collectives(self, device: str) -> List[Interval]:
        return intersect(union(collective_intervals(
            self.trace.devices[device])), [self.trace.window])

    def others(self, device: str) -> List[Interval]:
        return intersect(union([(s, e) for name, s, e
                                in self.trace.devices[device]
                                if not is_collective(name)]),
                         [self.trace.window])


# ---------------------------------------------------------------------------
# the breakdown the traced run prints
# ---------------------------------------------------------------------------


def host_label(host: Sequence[Event], t: int) -> str:
    """What the harness thread was in at time ``t``: its outermost and
    innermost enclosing events."""
    inside = [(s, -e, name) for name, s, e in host if s <= t < e]
    names = [name for _, _, name in sorted(inside)]
    if not names:
        return "host outside any span"
    return names[0] if len(names) == 1 else f"{names[0]} > {names[-1]}"


def breakdown(view: View, top: int = 10) -> dict:
    dev = view.busiest()
    if dev is None:
        return {"device_ops": [], "idle_gaps": []}
    lo, hi = view.trace.window
    per: Dict[str, int] = {}
    for name, s, e in view.trace.devices[dev]:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            per[name] = per.get(name, 0) + d
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps = subtract([view.trace.window], view.busy[dev])
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[name, ns * 1e-9] for name, ns in ops],
            "idle_gaps": [[host_label(view.trace.host, (s + e) // 2),
                           (e - s) * 1e-9] for s, e in gaps]}
