"""From a profiler trace (``.xplane.pb``) to busy and idle time, per-kernel
device time and the breakdown, read with ``jax.profiler.ProfileData``.

Device planes are ``/device:TPU:<n>``.  Their op events are on the line
``XLA Ops``, named by the op's HLO text (``%knn_tile_topk.10 = (...)
custom-call(...), ...``); a loop's event spans the events of its body.  The
programs they belong to are on the line ``XLA Modules``.  Busy time is the
union of op intervals inside the measured window (the benchmark's own host
span ``bench.window``), averaged over the chips.  Device op times count the
innermost ops only, named ``<program>/<op>``.  Idle gaps are the holes in
the busy union on the first chip, each named by the innermost host span
open over its midpoint on the thread that holds the benchmark's spans.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
TOP = 10

Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    name: str           # ``<program>/<op>``, e.g. jit__brute_engine/%knn_tile_topk.10
    start_ns: float
    end_ns: float
    text: str           # the op's whole HLO text: result and operand shapes


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: List[Op]                       # first chip's innermost ops in the window
    gaps: List[Tuple[str, float]]       # the longest (host span, seconds)

    def kernel(self, pattern: str) -> List[Op]:
        """Ops whose own name (the part after ``<program>/``) matches
        ``pattern``, a regex."""
        rx = re.compile(pattern)
        return [o for o in self.ops if rx.search(o.name.rsplit("/", 1)[1])]

    def kernel_s(self, pattern: str) -> float:
        return sum(o.end_ns - o.start_ns for o in self.kernel(pattern)) / 1e9

    def breakdown(self) -> Dict[str, list]:
        per_name: Dict[str, float] = {}
        for o in self.ops:
            per_name[o.name] = per_name.get(o.name, 0.0) + (
                o.end_ns - o.start_ns) / 1e9
        top = sorted(per_name.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _innermost_only(events):
    """The events that hold no other event (a loop's event holds its
    body's)."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    holds = [False] * len(events)
    stack: List[int] = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            holds[stack[-1]] = True
        stack.append(i)
    return [ev for ev, h in zip(events, holds) if not h]


def _program(modules, t: float) -> str:
    """The name of the program (``XLA Modules`` event) running at ``t``,
    without its fingerprint."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2].split("(", 1)[0]
    return "?"


def _host_spans(pd):
    """Host events of the thread that holds the window span, and the
    window's (start, end)."""
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            events = list(line.events)
            for ev in events:
                if ev.name == WINDOW_SPAN:
                    spans = [(e.start_ns, e.end_ns, e.name) for e in events]
                    return spans, (ev.start_ns, ev.end_ns)
    return [], None


def _innermost(spans, t: float) -> str:
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "(no host span)"


def reduce(pd) -> Summary:
    spans, window = _host_spans(pd)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = window
    devices = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        events, modules = [], []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if ev.end_ns > lo and ev.start_ns < hi:
                    item = (max(ev.start_ns, lo), min(ev.end_ns, hi), ev.name)
                    (events if line.name == OPS_LINE else modules).append(item)
        devices.append((plane.name, events, sorted(modules)))
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    busy = [sum(e - s for s, e in union([ev[:2] for ev in events]))
            for _, events, _ in devices]
    if devices:
        _, events, modules = devices[0]
        first = [Op(f"{_program(modules, s)}/{text.split(' = ', 1)[0]}",
                    s, e, text) for s, e, text in _innermost_only(events)]
        held = union([ev[:2] for ev in events])
    else:
        first, held = [], []
    edges = [lo] + [t for iv in held for t in iv] + [hi]
    holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
             if edges[i + 1] > edges[i]]
    # Host spans that the window itself opens are the context; name a gap
    # by the innermost span inside the window.
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    longest = sorted(holes, key=lambda h: h[0] - h[1])[:TOP]
    gaps = [(_innermost(inner, (s + e) / 2), (e - s) / 1e9)
            for s, e in longest]
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=(sum(busy) / len(busy) / 1e9) if busy else 0.0,
                   ops=first, gaps=gaps)


def start(trace_dir: str) -> None:
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop_and_reduce(trace_dir: str) -> Summary:
    """Stop the trace, reduce it, and delete it from disk."""
    import jax
    jax.profiler.stop_trace()
    try:
        return reduce(load(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def load(trace_dir: str):
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(files[-1])
