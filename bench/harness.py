"""One run of one cell: set-up, warm-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``BENCHMARK.json`` names the cell, its configuration file
and its mix (``bench/traffic/<mix>.json``), and each metric's reader is
``bench/metrics/<metric>.py``.  The window drives one public entry,
``KNNIndex.query``, on an index made by ``KNNIndex.build`` with the default
``HybridConfig`` except ``k``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Callable, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
SAMPLE_ROWS = 2048           # rows whose whole-corpus scan decides `missed`


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


@dataclasses.dataclass
class CallRecord:
    """One served call: its host wall time, its queries, what it returned
    and the programs JAX compiled or loaded while it ran."""

    wall: float
    queries: np.ndarray
    ids: np.ndarray
    dists: np.ndarray
    source: np.ndarray
    stats: Optional[object]
    exclude_self: bool
    programs: int

    @property
    def n_queries(self) -> int:
        return len(self.queries)


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    calls: List[CallRecord]         # the measured window's calls
    window_s: float
    setup_s: float
    build_s: float
    select_eps_s: float
    peak_bytes: int
    n_corpus: int
    dim: int
    k: int
    device_kind: str
    window_programs: int = 0        # programs JAX compiled or loaded in it
    trace: Optional[object] = None  # xplane.Summary of the traced window


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise ValueError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(
        chips=int(w["chips"]),
        config=_read_json(os.path.join(root, cfg_entry["file"])),
        mix=_read_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
    )


def reader(name: str) -> Callable[[Context], Optional[float]]:
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoAccelerator(
            f"need {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
    return devs[:chips]


def compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``<checkout>/.jax_cache``.  Every program is
    cached, however quick its compile, so a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class ProgramServer:
    """The system under test: ``KNNIndex.build`` once, ``index.query`` per
    call, with the default ``HybridConfig`` except ``k``."""

    def __init__(self, corpus: np.ndarray, k: int):
        from repro.core.hybrid import HybridConfig
        from repro.runtime.knn_index import KNNIndex
        t0 = time.perf_counter()
        self.index = KNNIndex.build(corpus, HybridConfig(k=k))
        self.build_s = time.perf_counter() - t0
        self.select_eps_s = float(self.index.t_select_eps)

    def serve(self, queries: np.ndarray, exclude_self: bool):
        r = self.index.query(queries, exclude_self=exclude_self)
        return r.dists, r.ids, r.source, r.stats

    def close(self) -> None:
        self.index = None


class ControlServer:
    """The lower-precision control in the program's place."""

    build_s = 0.0
    select_eps_s = 0.0

    def __init__(self, corpus: np.ndarray, k: int):
        import reference
        self.ctl = reference.Bf16Control(corpus, k)

    def serve(self, queries: np.ndarray, exclude_self: bool):
        d, i = self.ctl.query(queries, exclude_self)
        return d, i, np.full((len(queries),), 3, np.int32), None

    def close(self) -> None:
        self.ctl = None


def serve_call(server, call, programs: "ProgramCounter") -> CallRecord:
    before = programs.count
    t0 = time.perf_counter()
    with _span("bench.query"):
        d, i, src, stats = server.serve(call.queries, call.exclude_self)
    wall = time.perf_counter() - t0
    return CallRecord(wall, call.queries, np.asarray(i), np.asarray(d),
                      np.asarray(src), stats, call.exclude_self,
                      programs.count - before)


class GcClock:
    """Counts Python's full (generation 2) collections while open, and the
    seconds they take."""

    def __init__(self):
        self.count, self.seconds, self._t0 = 0, 0.0, 0.0

    def _hook(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count += 1
            self.seconds += time.perf_counter() - self._t0

    def __enter__(self):
        gc.callbacks.append(self._hook)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._hook)


class ProgramCounter:
    """Counts the programs JAX compiles, or loads from its compile cache,
    from JAX's own monitoring events: one per jitted function at a shape
    the process has not run before, the program's engines included."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._hook)

    def _hook(self, event, duration_secs, **kwargs):
        if event == self.EVENT:
            self.count += 1


def compiles(rec: CallRecord) -> int:
    return int(getattr(rec.stats, "n_engine_compiles", 0) or 0)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, control: bool = False,
             log=None) -> dict:
    """One run.  Returns the result object; raises ``NoAccelerator``.
    ``control`` serves the bfloat16 control instead of the program."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = load_cell(root, workload)
    devs = devices(cell.chips)
    cache = compile_cache(root)
    import clouds
    import traffic
    import xplane

    cfg, mix = cell.config, cell.mix
    k = int(cfg["k"])
    t0 = time.perf_counter()
    with _span("bench.data"):
        corpus, scale = clouds.make_cloud(cfg["generator"], cfg["n_points"],
                                          cfg["n_dims"], seed)
        traffic.check(mix, corpus)
    t_data = time.perf_counter() - t0

    t0 = time.perf_counter()
    with _span("bench.build"):
        server = (ControlServer if control else ProgramServer)(corpus, k)
    t_build = time.perf_counter() - t0

    def call(draw: int, j: int) -> CallRecord:
        return serve_call(server, traffic.make_call(
            mix, cfg, corpus, scale, seed, draw, j), programs)

    # A fixed number of warm-up calls, of a draw of their own: set-up does
    # the same work in every run.
    programs = ProgramCounter()
    t0 = time.perf_counter()
    with _span("bench.warmup"):
        warm = [call(traffic.WARM, j) for j in range(int(mix["warm_calls"]))]
    t_warm = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: data {t_data:.3f} s, build {t_build:.3f} s"
        f" (KNNIndex.build {server.build_s:.3f} s, select_eps "
        f"{server.select_eps_s:.3f} s), warm-up {t_warm:.3f} s over "
        f"{len(warm)} call(s) of {[round(c.wall, 3) for c in warm]} s, "
        f"programs compiled or loaded per call {[c.programs for c in warm]}"
        f", engine compiles {[compiles(c) for c in warm]}; compile cache "
        f"{cache}")

    trace_dir = os.path.join(root, ".bench_trace", workload)
    if trace:
        xplane.start(trace_dir)
    calls: List[CallRecord] = []
    full_gc = GcClock()
    w0 = time.perf_counter()
    with _span("bench.window"), full_gc:
        while not calls or time.perf_counter() - w0 < seconds:
            calls.append(call(traffic.WINDOW, len(calls)))
    window_s = time.perf_counter() - w0
    window_programs = sum(c.programs for c in calls)
    summary = xplane.stop_and_reduce(trace_dir) if trace else None
    stats = devs[0].memory_stats() or {}
    peak_bytes = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in devs))
    n_q = sum(c.n_queries for c in calls)
    walls = sorted(c.wall for c in calls)
    slow = sorted(calls, key=lambda c: c.wall)[-3:]
    log(f"window {window_s:.3f} s: {len(calls)} calls, {n_q} queries, "
        f"engine compiles {sum(compiles(c) for c in calls)}, programs "
        f"compiled or loaded {window_programs}; call wall "
        f"median {walls[len(walls) // 2]:.4f} s, longest "
        f"{[(round(c.wall, 3), c.programs) for c in slow]} (s, programs); "
        f"{full_gc.count} full collections taking {full_gc.seconds:.3f} s; "
        f"peak_bytes_in_use {peak_bytes} (bytes_limit "
        f"{stats.get('bytes_limit')})")

    ctx = Context(calls=calls, window_s=window_s, setup_s=setup_s,
                  build_s=server.build_s, select_eps_s=server.select_eps_s,
                  peak_bytes=peak_bytes, n_corpus=len(corpus),
                  dim=corpus.shape[1], k=k, device_kind=devs[0].device_kind,
                  window_programs=window_programs, trace=summary)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace and summary is not None:
        for name in ("device_ops", "idle_gaps"):
            log(f"{name}: {summary.breakdown()[name]}")

    # The check runs after the window and the memory reading, with the
    # program's state freed.
    server.close()
    del server
    gc.collect()
    t0 = time.perf_counter()
    with _span("bench.reference"):
        verdict = judge(corpus, calls, k, seed, cfg["limits"])
    log(f"reference {time.perf_counter() - t0:.3f} s over "
        f"{verdict['checked']['rows']} rows ({verdict['checked']['rows_scanned']}"
        f" scanned over the corpus; rows per lane "
        f"{verdict['checked']['lanes']})")
    for name, (value, limit) in verdict["compared"].items():
        log(f"{name} {value!r} limit {limit!r}")

    dev = devs[0]
    result = {
        "correct": verdict["correct"],
        "attempted": n_q,
        "failed": verdict["failed_rows"],
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": peak_bytes},
    }
    if trace and summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    # A value that is not finite goes out as a string, so that the line
    # stays JSON.
    result["compared"] = {
        n: {"value": v if np.isfinite(v) else repr(v), "limit": lim}
        for n, (v, lim) in verdict["compared"].items()}
    return result


def judge(corpus, calls: List[CallRecord], k: int, seed: int,
          limits: dict) -> dict:
    """Hold every row the window's calls returned to the reference."""
    import reference
    queries = np.concatenate([c.queries for c in calls])
    excl = np.concatenate([
        np.arange(c.n_queries) if c.exclude_self
        else np.full((c.n_queries,), -1) for c in calls])
    if any(c.ids.shape != (c.n_queries, k) or c.dists.shape != c.ids.shape
           for c in calls):
        checked = {"bad_rows": sum(c.n_queries for c in calls),
                   "dist_rel_err": float("inf"), "missed": 0, "rows": 0,
                   "rows_scanned": 0, "lanes": {}}
    else:
        checked = reference.check(
            corpus, queries, np.concatenate([c.ids for c in calls]),
            np.concatenate([c.dists for c in calls]),
            np.concatenate([c.source for c in calls]), excl, seed,
            SAMPLE_ROWS)
    compared = {n: (checked[n], limits[n]) for n in
                ("bad_rows", "dist_rel_err", "missed")}
    correct = all(v <= lim for v, lim in compared.values())
    return {"correct": bool(correct), "failed_rows": checked["bad_rows"],
            "checked": checked, "compared": compared}
