"""The sparse engine's capacity gate: a row whose finest-level 3^m
neighbourhood holds more than ``sparse_budget`` rows goes straight to
the brute lane.  Soundness against the engine run ungated, identical
answers with and without the gate, the queue's routing with stub
engines, the gate off where the pyramid's levels do not nest, and the
count program's memory."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HybridConfig
from repro.core import grid as grid_lib
from repro.core import queue as queue_lib
from repro.core import sparse_knn as sparse_lib
from repro.runtime import KNNIndex

K = 4
M = 4
EPS = 0.2


def _clouds():
    """A tight cluster (N₀ ≈ 400), a loose one and a uniform background
    in 4-d; queries near corpus rows and beyond the grid (clipped)."""
    r = np.random.default_rng(5)
    db = np.concatenate([r.normal(0.0, 0.03, (400, M)),
                         r.normal(1.0, 0.25, (300, M)),
                         r.uniform(-2.0, 2.0, (300, M))])
    near = db[r.choice(len(db), 150)] + 0.02 * r.normal(size=(150, M))
    far = r.uniform(-4.0, 4.0, (50, M))
    return (db.astype(np.float32),
            np.concatenate([near, far]).astype(np.float32))


def _neighbourhood_population(g, q_coords):
    """|{p : |c_p − c_q|_∞ ≤ 1}| by direct comparison of cell coords —
    no range lookup."""
    pc = np.asarray(g.point_coords)
    qc = np.asarray(q_coords)
    return (np.abs(qc[:, None, :] - pc[None, :, :]) <= 1).all(-1).sum(1)


@pytest.mark.parametrize("budget", [64, 128, 256])
@pytest.mark.parametrize("foreign", [False, True], ids=["self", "foreign"])
def test_certified_rows_fit_the_budget_at_level_zero(budget, foreign):
    db, queries = _clouds()
    pts_r, perm = grid_lib.reorder_by_variance(jnp.asarray(db))
    q_r = jnp.asarray(queries)[:, perm] if foreign else None
    eps = jnp.float32(EPS)
    pyr = sparse_lib.build_pyramid(pts_r, eps, M)
    assert sparse_lib.levels_nest(pyr, 2.0)
    n_q = len(queries) if foreign else len(db)
    pops = []
    for g in pyr.levels:
        qc = (g.point_coords if q_r is None
              else grid_lib.compute_cell_coords(g, q_r[:, :M]))
        pops.append(_neighbourhood_population(g, qc))
    n0 = np.asarray(sparse_lib.finest_counts(pyr.levels[0], q_r,
                                             query_block=128))
    np.testing.assert_array_equal(n0, pops[0])
    for lvl, pop in enumerate(pops):
        assert (pop >= n0).all(), lvl

    res = sparse_lib.sparse_knn(
        pyr, pts_r, jnp.arange(n_q, dtype=jnp.int32), q_r, k=K,
        budget=budget, backend="ref", exclude_self=not foreign)
    cert = np.asarray(res.certified)
    assert cert.any() and (n0 > budget).any()
    assert (n0[cert] <= budget).all()


def _index(level_scale=2.0, **kw):
    db, queries = _clouds()
    cfg = HybridConfig(k=K, m=M, gamma=0.3, rho=0.15, n_batches=2,
                       sparse_budget=64, level_scale=level_scale,
                       online_rebalance=False, **kw)
    return KNNIndex.build(db, cfg, epsilon=EPS), db, queries


@pytest.mark.parametrize("mutated", [False, True], ids=["clean", "mutated"])
@pytest.mark.parametrize("backend", ["ref", "fused"])
@pytest.mark.parametrize("foreign", [False, True], ids=["self", "foreign"])
def test_gated_and_ungated_queries_answer_alike(backend, foreign, mutated):
    gated, db, queries = _index(backend=backend)
    ungated, _, _ = _index(backend=backend)
    ungated._live[0].levels_nest = False
    if mutated:
        # Tombstones stay in the base grid's ranges, which N₀ counts too.
        for index in (gated, ungated):
            index.insert(db[:20] + 0.01)
            index.delete(np.arange(0, 400, 20))
    q = queries if foreign else None
    a = gated.query(q, exclude_self=not foreign)
    b = ungated.query(q, exclude_self=not foreign)
    assert a.stats.n_sparse_gated > 0 and b.stats.n_sparse_gated == 0
    assert (a.stats.n_sparse_engine_total + a.stats.n_sparse_gated
            == b.stats.n_sparse_engine_total)
    assert a.stats.n_sparse_engine_total > 0
    if not mutated:     # the tombstones' k headroom certifies no row here
        assert (a.source == 1).any()      # the engine still certifies
    np.testing.assert_array_equal(a.source, b.source)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.dists, b.dists)


@pytest.mark.parametrize("case", ["level_scale", "int32_cap"])
def test_gate_is_off_where_levels_do_not_nest(case):
    if case == "level_scale":
        index, _, queries = _index(level_scale=1.5)
    else:
        # ε far below extent / 214, the cells per dim that int32 ids
        # allow at m = 4: the cap widens level 0's edge, not coarser ones.
        db, queries = _clouds()
        cfg = HybridConfig(k=K, m=M, sparse_budget=64, n_batches=2)
        index = KNNIndex.build(db, cfg, epsilon=0.005)
    assert not index._live[0].levels_nest
    for q in (queries, None):
        s = index.query(q, exclude_self=q is None).stats
        assert s.n_sparse_gated == 0 and s.n_sparse_engine_total > 0


def test_queue_sends_gated_rows_to_the_brute_lane_only():
    """Gated ids from every round (initial, demoted backlog, terminal
    dense failures) reach ``brute_fn`` once and never ``sparse_fn``;
    the ρ floor counts them with the sparse rows."""
    npts, k = 96, 2
    home = np.arange(npts)       # densest dequeued first: 63, 62, ...
    dense_ids = np.arange(0, 64, dtype=np.int32)
    sparse_ids = np.arange(64, npts, dtype=np.int32)
    ok = np.ones(npts, bool)
    ok[[63, 0, 64, 70, 95]] = False   # a dense failure, a demoted row,
    seen = {"sparse": [], "brute": []}  # three initial sparse rows

    def answer(ids):
        nids = (np.asarray(ids)[:, None] + np.arange(1, k + 1)) % npts
        return np.full((len(ids), k), 0.5, np.float32), nids.astype(np.int32)

    def dense_fn(ids):
        return (*answer(ids), np.isin(ids, [63, 62]), 1.0)

    def sparse_fn(ids):
        seen["sparse"].append(np.asarray(ids))
        handle = queue_lib.AsyncEngineCall(
            (*answer(ids), np.asarray(ids) != 9))
        handle.elapsed = 1e-6 * len(ids)    # cheap: demotion fires
        return handle

    def brute_fn(ids):
        seen["brute"].append(np.asarray(ids))
        return answer(ids)

    fd, fi, src, rep = queue_lib.run_work_queue(
        npts=npts, k=k, dense_ids=dense_ids, sparse_ids=sparse_ids,
        home_counts=home, dense_fn=dense_fn, sparse_fn=sparse_fn,
        brute_fn=brute_fn, n_batches=4, online_rebalance=True,
        sync_t1_after=1, demote_quantum=1,
        min_sparse=len(sparse_ids), sparse_ok=ok)
    to_sparse = np.concatenate(seen["sparse"])
    gated = np.nonzero(~ok)[0]
    assert rep.n_rebalanced > 0 and rep.n_failed == 2
    assert ok[to_sparse].all() and 62 in to_sparse and src[62] == 1
    to_brute, = seen["brute"]
    assert sorted(to_brute) == sorted(set(gated) | {9})
    assert (src[gated] == 2).all()
    assert rep.n_sparse_gated == len(gated)
    assert rep.n_sparse_engine_total == len(to_sparse)
    assert rep.n_uncertified == 1
    assert (src != 0).sum() >= len(sparse_ids) and (fi >= 0).all()


def test_queue_skips_a_round_the_gate_empties():
    npts, k = 16, 1

    def sparse_fn(ids):
        raise AssertionError("no row passes the gate")

    *_, rep = queue_lib.run_work_queue(
        npts=npts, k=k, dense_ids=np.arange(8, dtype=np.int32),
        sparse_ids=np.arange(8, npts, dtype=np.int32),
        home_counts=np.ones(npts), sparse_fn=sparse_fn,
        dense_fn=lambda ids: (np.zeros((len(ids), k), np.float32),
                              np.zeros((len(ids), k), np.int32),
                              np.ones(len(ids), bool), 1.0),
        brute_fn=lambda ids: (np.zeros((len(ids), k), np.float32),
                              np.zeros((len(ids), k), np.int32)),
        min_sparse=8, sparse_ok=np.zeros(npts, bool))
    assert rep.n_sparse_rounds == 0 and rep.n_sparse_gated == npts
    assert rep.n_uncertified == 0


def test_count_program_memory_at_q8192_m6():
    """No (|Q|, 3^m) array at full |Q|: the count program's temporaries
    at 8,192 query rows and m = 6 stay under 16 MiB (the (Q, 729, 6)
    int32 neighbour coordinates alone would be 143 MB)."""
    r = np.random.default_rng(0)
    pts = jnp.asarray(r.uniform(0.0, 1.0, (4096, 8)).astype(np.float32))
    level0 = grid_lib.build_grid(pts, jnp.float32(0.2), 6,
                                 materialize_points=False)
    queries = jax.ShapeDtypeStruct((8192, 8), jnp.float32)
    compiled = sparse_lib.finest_counts.lower(
        level0, queries, query_block=128).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < 16 * 2**20
