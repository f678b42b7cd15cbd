"""Chip smoke test: the hybrid KNN index's main path on one TPU.

    python chip_smoke.py              # one chip: serving + self-join phases
    python chip_smoke.py --chips 4    # sharded index on four chips vs one

Both phases run in this one process (a chip belongs to one process) and
go through the entry points a user calls, with the default
``HybridConfig`` except ``k=16``:

  serving    ``KNNIndex.build`` on a SuSy-shaped cloud (5,000,000 x 18
             f32, seed 0, ~360 MB on the device), then
             ``index.query`` on 3 foreign batches of 16,384 queries
             (seed 1): batch 1 cold, batches 2 and 3 steady, which
             must compile no engine;
  self-join  one ``HybridKNNJoin(cfg).join`` on a CHist-shaped cloud
             (68,040 x 32, the paper's size).

Each phase is checked against a float64 numpy brute force over the full
corpus on a seeded sample of 512 query rows: distances within 1e-4
relative, ids equal except where the oracle ties at the k-th distance.
The script also fails when the platform is not a TPU, when the backend
does not resolve to the fused engine, when no query is run through the
dense engine, or when the dense or brute engine's compiled program
holds no Pallas kernel (``tpu_custom_call``).  Queries the dense engine
cannot certify fail over to the sparse and brute lanes, so the served
answers' exactness may rest on the brute lane alone; a dense drill
(one densest-cell tile at a budget that holds its neighbourhood) holds
the streaming kernel's own answers to the oracle.

``--chips 4`` runs only the sharded path: the same SuSy cloud built
over ``make_serving_mesh(4)``, the same batches, ids and distances
compared with the single-device index on 512 rows of each batch, each
shard's device printed.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

K = 16
SUSY_ROWS = 5_000_000
CHIST_ROWS = 68_040
QUERY_BATCH = 16_384
N_QUERY_BATCHES = 3
ORACLE_ROWS = 512
RTOL = 1e-4
ORACLE_CHUNK = 131_072
CONFIG = dict(k=K)          # the default HybridConfig, except k
DRILL_BUDGET = 1 << 20      # candidate rows the dense drill may scan
BACKEND = "fused"           # what backend="auto" must resolve to


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# float64 oracle
# ---------------------------------------------------------------------------

def oracle_knn64(corpus: np.ndarray, queries: np.ndarray, k: int,
                 exclude=None):
    """Exact float64 k+1 nearest corpus rows of each query: candidates
    from the ||q||² + ||c||² − 2q·c expansion, chunked over the corpus,
    then re-ranked on directly computed differences.  ``exclude[i]`` is
    a corpus id query i may not report (−1: none).  Returns (d (Q, k+1)
    ascending Euclidean, ids (Q, k+1))."""
    q = queries.astype(np.float64)
    nq = len(q)
    keep = k + 1 + 4                      # slack for expansion rounding
    qq = np.sum(q * q, axis=1)[:, None]
    best_d = np.full((nq, 0), np.inf)
    best_i = np.zeros((nq, 0), np.int64)
    rows = np.arange(nq)[:, None]
    for lo in range(0, len(corpus), ORACLE_CHUNK):
        c = corpus[lo:lo + ORACLE_CHUNK].astype(np.float64)
        d2 = qq + np.sum(c * c, axis=1)[None, :] - 2.0 * (q @ c.T)
        if exclude is not None:
            own = exclude - lo
            hit = (own >= 0) & (own < len(c))
            d2[np.nonzero(hit)[0], own[hit]] = np.inf
        t = min(keep, d2.shape[1])
        part = np.argpartition(d2, t - 1, axis=1)[:, :t]
        best_d = np.concatenate([best_d, d2[rows, part]], axis=1)
        best_i = np.concatenate([best_i, part + lo], axis=1)
        sel = np.argsort(best_d, axis=1)[:, :keep]
        best_d = best_d[rows, sel]
        best_i = best_i[rows, sel]
    cand = corpus[best_i].astype(np.float64)               # (Q, keep, D)
    exact = np.sum((cand - q[:, None, :]) ** 2, axis=2)
    exact[~np.isfinite(best_d)] = np.inf
    order = np.argsort(exact, axis=1, kind="stable")[:, :k + 1]
    return (np.sqrt(exact[rows, order]), best_i[rows, order])


def compare_to_oracle(name, corpus, queries, dists, ids, k, exclude=None):
    """Hold the engine's (dists, ids) rows to the float64 oracle: every
    distance within RTOL relative, the id set equal except where the
    oracle ties at the k-th distance (then every reported id must still
    be a true neighbor within that distance)."""
    t0 = time.perf_counter()
    od, oi = oracle_knn64(corpus, queries, k, exclude)
    check(np.all(np.isfinite(dists)), f"{name}: non-finite distances")
    err = np.abs(dists.astype(np.float64) - od[:, :k])
    rel = err / np.maximum(od[:, :k], 1e-12)
    bad_d = int(np.sum(rel > RTOL))
    tie = (od[:, k] - od[:, k - 1]) <= RTOL * od[:, k - 1]
    same = np.array([set(a) == set(b) for a, b in zip(ids, oi[:, :k])])
    true_d = np.sqrt(np.sum(
        (corpus[np.clip(ids, 0, None)].astype(np.float64)
         - queries.astype(np.float64)[:, None, :]) ** 2, axis=2))
    valid = np.all(ids >= 0, axis=1) & np.all(
        true_d <= od[:, k - 1:k] * (1 + RTOL), axis=1)
    distinct = np.array([len(set(r)) == k for r in ids])
    bad_i = int(np.sum(~same & ~(tie & valid & distinct)))
    log(f"[{name}] oracle: {len(queries)} rows vs float64 brute force over "
        f"{len(corpus)} points in {time.perf_counter() - t0:.1f} s; "
        f"max |dist - oracle| = {err.max():.3e} (max rel {rel.max():.3e}); "
        f"rows with k-th tie = {int(tie.sum())}, id-set differences = "
        f"{int((~same).sum())}")
    check(bad_d == 0, f"{name}: {bad_d} distances off by > {RTOL} relative")
    check(bad_i == 0, f"{name}: {bad_i} rows with wrong neighbor ids")
    return float(err.max())


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def make_queries(corpus: np.ndarray, n: int, jitter: float, seed: int):
    """Foreign queries from the corpus' own distribution: corpus rows
    picked by ``seed`` plus Gaussian jitter (none equals a corpus row)."""
    rng = np.random.default_rng(seed)
    rows = rng.choice(len(corpus), n, replace=False)
    noise = rng.normal(0.0, jitter, (n, corpus.shape[1]))
    return (corpus[rows] + noise).astype(np.float32)


def kernel_in(executable) -> bool:
    return "tpu_custom_call" in executable.as_text()


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def susy_setup():
    from repro.core import HybridConfig
    from repro.data import pointclouds

    spec = pointclouds.SPECS["susy"]
    t0 = time.perf_counter()
    points = pointclouds.make_cloud(spec, seed=0, n_override=SUSY_ROWS)
    queries = make_queries(points, N_QUERY_BATCHES * QUERY_BATCH,
                           spec.cluster_sigma / 4, seed=1)
    log(f"[serving] SuSy-shaped cloud {points.shape[0]} x {points.shape[1]} "
        f"f32 (seed 0) + {N_QUERY_BATCHES} x {QUERY_BATCH} queries (seed 1) "
        f"made on the host in {time.perf_counter() - t0:.1f} s")
    batches = np.split(queries, N_QUERY_BATCHES)
    return HybridConfig(**CONFIG), points, batches


def serve_batches(name, index, batches):
    """Answer every batch and return the results.  Batch 1 is cold;
    batches 2.. are steady and must compile nothing."""
    out = []
    for b, q in enumerate(batches):
        label = "cold" if b == 0 else "steady"
        t0 = time.perf_counter()
        res = index.query(q)
        dt = time.perf_counter() - t0
        s = res.stats
        log(f"[{name}] batch {b + 1} ({label}): {dt:.3f} s wall, "
            f"{len(q) / dt:.0f} queries/s; n_dense={s.n_dense} "
            f"n_sparse={s.n_sparse} n_failed={s.n_failed} "
            f"n_uncertified={s.n_uncertified} "
            f"n_rebalanced={s.n_rebalanced} (rho_online "
            f"{s.rho_online:.4f}) served dense/sparse/brute="
            f"{[int(np.sum(res.source == j)) for j in range(3)]}; "
            f"engine compiles={s.n_engine_compiles} "
            f"compile_counts={dict(index.compile_counts)}")
        if label == "steady":
            check(s.n_engine_compiles == 0,
                  f"{name}: steady batch {b + 1} compiled "
                  f"{s.n_engine_compiles} engines")
        out.append(res)
    return out


def check_dense_ran(name, results) -> None:
    """Queries must have gone through the dense engine.  Those it
    cannot certify (fewer than k within ε, or a candidate-budget
    overflow) fail over to the sparse and brute lanes (paper §V-E), so
    the count it answered is reported, not required."""
    ran = sum(r.stats.n_dense for r in results)
    served = [sum(int(np.sum(r.source == j)) for r in results)
              for j in range(3)]
    log(f"[{name}] dense engine: {ran} queries run, {served[0]} answered "
        f"(the rest failed over to the sparse/brute lanes)")
    if served[0] == 0 and served[1] == 0:
        log(f"[{name}] every served answer came from the brute lane: the "
            f"served path's exactness rests on it alone")
    check(ran > 0, f"{name}: no query went through the dense engine")


def sample_rows(n_total: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n_total, ORACLE_ROWS, replace=False))


def phase_serving():
    import jax
    from repro.runtime import KNNIndex

    cfg, points, batches = susy_setup()
    t0 = time.perf_counter()
    index = KNNIndex.build(points, cfg)
    log(f"[serving] build (cold): {time.perf_counter() - t0:.3f} s "
        f"(eps select {index.t_select_eps:.3f} s, grid+pyramid "
        f"{index.t_build:.3f} s); eps={index.eps:.6g}")
    log(f"backend: {index.backend}")
    check(index.backend == BACKEND,
          f"backend resolved to {index.backend!r}, not {BACKEND!r}")
    results = serve_batches("serving", index, batches)
    check_dense_ran("serving", results)
    check(kernel_in(index.executables["dense"]),
          "serving: dense engine holds no tpu_custom_call")
    log("[serving] dense engine compiled text holds tpu_custom_call")

    queries = np.concatenate(batches)
    dists = np.concatenate([r.dists for r in results])
    ids = np.concatenate([r.ids for r in results])
    pick = sample_rows(len(queries), seed=2)
    compare_to_oracle("serving", points, queries[pick], dists[pick],
                      ids[pick], K)
    drill_dense(index, points)
    log(f"[serving] peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")
    return index


def drill_dense(index, points) -> None:
    """At this size the default ``dense_budget`` fails every dense query
    over to the other lanes, so the served answers never exercise the
    dense kernel's arithmetic.  Drive the dense engine directly on one
    tile of the densest grid cell's points (a self-join tile), with a
    budget that holds their 3^m neighbourhood, and hold its rows to the
    float64 oracle."""
    import jax.numpy as jnp
    from repro.core import dense_join as dense_lib

    g = index.grid
    slot = int(np.argmax(np.asarray(g.cell_counts)))
    start = int(np.asarray(g.cell_starts)[slot])
    ids = np.asarray(g.order)[start:start + 128].astype(np.int32)
    t0 = time.perf_counter()
    res = dense_lib.dense_join(
        g, index.points_r, jnp.asarray(ids), jnp.float32(index.eps),
        k=K, budget=DRILL_BUDGET, backend=index.backend)
    failed = np.asarray(res.failed)
    log(f"[dense-drill] {len(ids)} points of the densest cell "
        f"({int(np.asarray(g.cell_counts)[slot])} rows), budget "
        f"{DRILL_BUDGET}: {int((~failed).sum())} answered in "
        f"{time.perf_counter() - t0:.3f} s (compile included)")
    check(not failed.all(), "dense drill: every row failed")
    ok = ~failed
    dists = np.sqrt(np.asarray(res.dists)[ok])
    compare_to_oracle("dense-drill", points, points[ids[ok]], dists,
                      np.asarray(res.ids)[ok], K, exclude=ids[ok])


def phase_self_join():
    import jax
    from repro.core import HybridConfig, HybridKNNJoin
    from repro.data import pointclouds

    spec = pointclouds.SPECS["chist"]
    points = pointclouds.make_cloud(spec, seed=0, n_override=CHIST_ROWS)
    log(f"[self-join] CHist-shaped cloud {points.shape[0]} x "
        f"{points.shape[1]} f32 (seed 0)")
    joiner = HybridKNNJoin(HybridConfig(**CONFIG))
    t0 = time.perf_counter()
    res = joiner.join(points)
    dt = time.perf_counter() - t0
    s = res.stats
    log(f"[self-join] join (cold): {dt:.3f} s wall (query phase "
        f"{s.t_wall:.3f} s); n_dense={s.n_dense} n_sparse={s.n_sparse} "
        f"n_failed={s.n_failed} n_uncertified={s.n_uncertified} "
        f"served dense/sparse/brute="
        f"{[int(np.sum(res.source == j)) for j in range(3)]}; "
        f"engine compiles={s.n_engine_compiles} "
        f"compile_counts={dict(joiner.session.compile_counts)}")
    check(joiner.session.backend == BACKEND,
          f"self-join backend {joiner.session.backend!r}, not {BACKEND!r}")
    check_dense_ran("self-join", [res])
    execs = joiner.session.executables
    check(kernel_in(execs["dense"]),
          "self-join: dense engine holds no tpu_custom_call")
    pick = sample_rows(len(points), seed=3)
    compare_to_oracle("self-join", points, points[pick], res.dists[pick],
                      res.ids[pick], K, exclude=pick)
    log(f"[self-join] peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")
    return execs


def check_brute(*execs_dicts) -> None:
    """The brute (certification) lane must have run compiled Pallas in
    at least one phase."""
    brute = [e["brute"] for e in execs_dicts if "brute" in e]
    check(bool(brute), "no phase exercised the brute engine")
    check(all(kernel_in(ex) for ex in brute),
          "brute engine holds no tpu_custom_call")
    log("brute engine compiled text holds tpu_custom_call")


def phase_sharded(n_chips: int):
    import jax
    from repro.launch.mesh import make_serving_mesh
    from repro.runtime import KNNIndex
    from repro.runtime.knn_index import _ENGINE_CACHE

    cfg, points, batches = susy_setup()
    t0 = time.perf_counter()
    single = KNNIndex.build(points, cfg)
    log(f"[single] build (cold): {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    sharded = KNNIndex.build(points, cfg, mesh=make_serving_mesh(n_chips))
    log(f"[sharded] build (cold): {time.perf_counter() - t0:.3f} s")
    log(f"backend: {sharded.backend}")
    check(sharded.backend == BACKEND,
          f"backend resolved to {sharded.backend!r}, not {BACKEND!r}")
    shard_devs = [shard.points_r.sharding.device_set
                  for shard in sharded.shards]
    for p, devs in enumerate(shard_devs):
        log(f"[sharded] shard {p}: data on {sorted(str(d) for d in devs)}")
    owners = [next(iter(d)) for d in shard_devs]
    check(all(len(d) == 1 for d in shard_devs)
          and len(set(owners)) == n_chips,
          f"shards do not sit on {n_chips} distinct devices: {shard_devs}")

    # The single-device index answers only the compared rows: exact
    # answers do not depend on the batch around them, and a full batch
    # costs it a brute-lane pass over all 5M rows per query.
    max_diff = 0.0
    for b, q in enumerate(batches):
        t0 = time.perf_counter()
        got = sharded.query(q)
        dt = time.perf_counter() - t0
        log(f"[sharded] batch {b + 1} ({'cold' if b == 0 else 'steady'}): "
            f"{dt:.3f} s wall, {len(q) / dt:.0f} queries/s; "
            f"n_dense={got.stats.n_dense} n_failed={got.stats.n_failed} "
            f"n_uncertified={got.stats.n_uncertified} t_merge="
            f"{got.stats.t_merge:.3f} s; engine compiles="
            f"{got.stats.n_engine_compiles} "
            f"compile_counts={dict(sharded.compile_counts)}")
        pick = sample_rows(len(q), seed=10 + b)
        want = single.query(q[pick])
        check(np.array_equal(want.ids, got.ids[pick]),
              f"batch {b + 1}: sharded ids differ from single-device ids "
              f"in {int(np.sum(np.any(want.ids != got.ids[pick], 1)))} "
              f"rows")
        check(np.allclose(want.dists, got.dists[pick], rtol=2e-6,
                          atol=2e-6),
              f"batch {b + 1}: sharded distances differ")
        max_diff = max(max_diff,
                       float(np.abs(want.dists - got.dists[pick]).max()))
    log(f"[sharded] ids equal to the single-device index on "
        f"{ORACLE_ROWS} rows of each of {N_QUERY_BATCHES} batches; "
        f"max |dist diff| = {max_diff:.3e}")

    # Each shard's engines must be compiled for, and run on, its chip.
    engine_devs = set()
    for key, ex in _ENGINE_CACHE.items():
        if key[0] == "dense":
            for sh in jax.tree_util.tree_leaves(ex.input_shardings):
                engine_devs.add(frozenset(sh.device_set))
    log(f"[sharded] dense executables run on: "
        f"{sorted(sorted(str(d) for d in s) for s in engine_devs)}")
    for owner in owners:
        check(frozenset([owner]) in engine_devs,
              f"no dense engine compiled for {owner}")
    for d in jax.devices():
        log(f"[sharded] {d}: peak_bytes_in_use {peak_bytes(d)}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-index path on 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"FAIL: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"FAIL: {args.chips} chips asked, {len(devices)} present",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.utils import init_compile_cache

    log(f"device_kind: {dev.device_kind} ({len(devices)} devices)")
    log(f"compile cache: {init_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_sharded(args.chips)
        else:
            serving = phase_serving()
            join_execs = phase_self_join()
            check_brute(serving.executables, join_execs)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    log(f"total wall: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
