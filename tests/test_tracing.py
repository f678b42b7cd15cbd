"""Spans and counters inside the query path: the engines' work and
overflow counters against counts taken independently from the grid, the
per-call compile tally, and the spans a profiler trace of one
``KNNIndex.query`` holds."""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_mixture
from repro.core import HybridConfig
from repro.core import dense_join as dense_lib
from repro.core import grid as grid_lib
from repro.core import sparse_knn as sparse_lib
from repro.runtime import KNNIndex, clear_engine_cache, trace
from repro.utils import round_up

BLOCK = 128
K = 3


@pytest.fixture(scope="module")
def cloud():
    """A clustered cloud on an ε-grid, and an odd-sized query-id subset
    (a padded last tile)."""
    pts = make_mixture(300, 100, dim=6, seed=1)
    pts_r = grid_lib.reorder_by_variance(jnp.asarray(pts))[0]
    eps = jnp.float32(0.25)
    idx = grid_lib.build_grid(pts_r, eps, 4)
    qids = jnp.arange(0, len(pts), 3, dtype=jnp.int32)
    return pts_r, idx, qids, eps


def _tiles(idx, qids):
    """The engine's cell-sorted tiles of ``qids``, and each tile's real
    rows' 3^m ranges (padding rows zeroed)."""
    qpad = round_up(len(qids), BLOCK)
    q = jnp.full((qpad,), -1, jnp.int32).at[: len(qids)].set(qids)
    tiles, _ = grid_lib.group_queries_by_cell(idx, q, BLOCK)
    for t in tiles:
        starts, counts = grid_lib.neighbor_ranges(
            idx, idx.point_coords[jnp.clip(t, 0)])
        counts = jnp.where((t >= 0)[:, None], counts, 0)
        yield int(jnp.sum(t >= 0)), starts, counts


def _own_totals(idx, qids):
    _, counts = grid_lib.neighbor_ranges(idx, idx.point_coords[qids])
    return np.asarray(jnp.sum(counts, axis=1))


def _touched_blocks(starts, counts, block_c):
    s, c = np.asarray(starts).ravel(), np.asarray(counts).ravel()
    return len({p // block_c for a, n in zip(s, c) if n
                for p in range(a, a + n)})


@pytest.mark.parametrize("backend", ["ref", "interpret", "fused"])
def test_dense_pair_counts_equal_the_candidate_union(cloud, backend):
    """``pairs``/``cand_rows`` are each tile's real rows × the union of
    their cell ranges (``tile_shared_candidates``); the ref backend scores
    each row against its own ranges."""
    pts_r, idx, qids, eps = cloud
    res = dense_lib.dense_join(idx, pts_r, qids, eps, k=K, budget=2048,
                               backend=backend)
    assert int(res.n_overflow) == 0
    if backend == "ref":
        own = _own_totals(idx, qids)
        want_pairs = want_rows = int(own.sum())
    else:
        want_pairs = want_rows = 0
        for rows, starts, counts in _tiles(idx, qids):
            _, _, total, ovf = grid_lib.tile_shared_candidates(
                idx, starts, counts, 2048)
            assert not bool(ovf)
            want_pairs += rows * int(total)
            want_rows += int(total)
    assert int(res.pairs) == want_pairs > 0
    assert int(res.cand_rows) == want_rows


@pytest.mark.parametrize("backend", ["ref", "interpret", "fused"])
def test_dense_overflow_counts_rows_failed_by_overflow(cloud, backend):
    """With a tiny budget, ``n_overflow`` is the real rows whose candidate
    set did not fit: per row on ref, per tile on the tiled backends (the
    union past the budget rounded to whole candidate blocks, or, on the
    fused path, touching more corpus blocks than its DMA schedule)."""
    pts_r, idx, qids, eps = cloud
    budget, block_c = 8, 128
    res = dense_lib.dense_join(idx, pts_r, qids, eps, k=K, budget=budget,
                               block_c=block_c, backend=backend)
    if backend == "ref":
        want = int((_own_totals(idx, qids) > budget).sum())
    else:
        cap = round_up(budget, block_c)
        nblk = min(cap // block_c + dense_lib.PREFETCH_BLOCK_SLACK,
                   -(-idx.n_points // block_c))
        want = 0
        for rows, starts, counts in _tiles(idx, qids):
            total = int(grid_lib.tile_shared_candidates(
                idx, starts, counts, cap)[2])
            over = (total > budget or _touched_blocks(
                starts, counts, block_c) > nblk) if backend == "fused" else (
                total > cap)
            want += rows * over
    assert 0 < int(res.n_overflow) == want
    assert want <= int(np.asarray(res.failed).sum())


def test_sparse_overflow_counts_overflowed_uncertified_rows(cloud):
    """``n_overflow`` is the uncertified real rows whose candidate count at
    the pyramid level they ended on exceeds the budget."""
    pts_r, idx, qids, eps = cloud
    budget = 16
    pyr = sparse_lib.build_pyramid(pts_r, eps, 4)
    res = sparse_lib.sparse_knn(pyr, pts_r, qids, k=K, budget=budget,
                                backend="ref")
    level = np.asarray(res.level)
    totals = np.stack([_own_totals(g, qids) for g in pyr.levels])
    over = totals[level, np.arange(len(qids))] > budget
    want = int((over & ~np.asarray(res.certified)).sum())
    assert 0 < int(res.n_overflow) == want


def _db_and_queries():
    db = make_mixture(420, 180, dim=6, seed=3)
    r = np.random.default_rng(1)
    near = 0.05 * r.normal(size=(90, 6))
    far = r.uniform(3.0, 6.0, (45, 6))       # sparse rows the brute lane ends
    return db, np.concatenate([near, far]).astype(np.float32)


@pytest.mark.parametrize("backend", ["ref", "interpret", "fused"])
def test_query_stats_carry_the_engine_counters(backend):
    """Through the work queue into ``JoinStats``: at default budgets the
    dense lane answers without overflow; at tiny ones every dense row
    overflows, and so does every row the sparse engine sees (its level
    holds ≥ sel_factor·(k+1) candidates by construction)."""
    db, queries = _db_and_queries()
    for dense_budget, sparse_budget, tiny in ((2048, 512, False),
                                              (8, 4, True)):
        cfg = HybridConfig(k=K, m=4, gamma=0.3, rho=0.15, n_batches=2,
                           backend=backend, online_rebalance=False,
                           dense_budget=dense_budget,
                           sparse_budget=sparse_budget)
        s = KNNIndex.build(db, cfg).query(queries).stats
        assert s.n_dense > 0 and s.dense_cand_rows > 0
        assert s.dense_pairs >= s.dense_cand_rows
        if tiny:
            assert s.n_dense_overflow == s.n_failed == s.n_dense
            assert (s.n_sparse_overflow == s.n_uncertified
                    == s.n_sparse_engine_total)
        else:
            assert s.n_dense_overflow == 0 and s.n_failed < s.n_dense
            assert 0 < s.n_sparse_overflow <= s.n_uncertified


def test_cold_call_compiles_inside_knn_spans_and_warm_call_none():
    clear_engine_cache()
    db, queries = _db_and_queries()
    cfg = HybridConfig(k=4, m=3, gamma=0.3, rho=0.15, n_batches=2,
                       online_rebalance=False)
    index = KNNIndex.build(db, cfg)
    cold = index.query(queries).stats
    assert cold.n_programs_compiled > 0 and cold.t_compile > 0
    assert sum(cold.compiles_by_span.values()) == cold.n_programs_compiled
    assert all(s.startswith("knn.") for s in cold.compiles_by_span)
    assert cold.compiles_by_span.get("knn.prepare", 0) >= 2   # the engines
    warm = index.query(queries.copy()).stats
    assert warm.n_programs_compiled == 0
    assert warm.t_compile == 0.0 and warm.compiles_by_span == {}


def test_compiles_go_to_the_innermost_knn_span_of_their_thread():
    x = jnp.ones(3)
    before = trace.snapshot()
    with trace.span("knn.outer"), trace.span("other"):
        jax.jit(lambda v: v + 1)(x)                  # not a knn.* span
        with trace.span("knn.inner"):
            jax.jit(lambda v: v * 2)(x)
        # A compile on another thread sees that thread's spans (none).
        t = threading.Thread(target=lambda: jax.jit(lambda v: v - 1)(x))
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
    got = trace.since(before)
    assert got.by_span == {"knn.outer": 1, "knn.inner": 1, trace.OUTSIDE: 1}
    assert got.programs == 3 and got.seconds > 0


def test_profiler_trace_holds_the_query_spans(tmp_path):
    db, queries = _db_and_queries()
    cfg = HybridConfig(k=K, m=4, gamma=0.3, rho=0.15, n_batches=2,
                       online_rebalance=False)
    index = KNNIndex.build(db, cfg)
    index.query(queries)                          # compile out of the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            res = index.query(queries.copy())
    finally:
        jax.profiler.stop_trace()
    assert (res.source == 2).any(), "the brute lane must run"
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    lines = [line for plane in pd.planes for line in plane.lines
             if any(e.name == "bench.window" for e in line.events)]
    assert len(lines) == 1                        # the calling thread
    spans = {}
    for e in lines[0].events:
        spans.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    (q0, q1), = spans["knn.query"]
    for name in ("knn.split", "knn.prepare", "knn.dense.wait",
                 "knn.dense.copy", "knn.sparse.dispatch", "knn.sparse.wait",
                 "knn.sparse.copy", "knn.brute.wait", "knn.brute.copy",
                 "knn.finalize"):
        assert spans.get(name), name
        assert all(q0 <= s and e <= q1 for s, e in spans[name]), name
