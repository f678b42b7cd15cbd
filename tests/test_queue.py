"""Work-queue scheduler + JoinSession: exactness oracle, scheduling
invariants, and the compile-count probe (ISSUE 1 acceptance tests)."""
import math
import time

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_mixture
from repro.core import HybridConfig, HybridKNNJoin, brute_knn
from repro.core import queue as queue_lib
from repro.runtime.session import JoinSession


def _uniform(n=320, dim=6, seed=3):
    r = np.random.default_rng(seed)
    return r.uniform(-1.0, 1.0, (n, dim)).astype(np.float32)


def _clustered(seed=4):
    return make_mixture(260, 90, dim=6, seed=seed)


CLOUDS = {"uniform": _uniform, "clustered": _clustered}


def _brute_oracle(pts, k):
    d, i = brute_knn(
        jnp.asarray(pts), jnp.asarray(pts),
        jnp.arange(len(pts), dtype=jnp.int32), k=k, kernel_mode="ref",
    )
    return np.sqrt(np.maximum(np.asarray(d), 0.0)), np.asarray(i)


# ---------------------------------------------------------------------------
# exactness oracle: JoinSession == brute_knn over the parameter grid
# ---------------------------------------------------------------------------

PARAM_GRID = [
    # (k, gamma, rho, n_batches)
    (1, 0.0, 0.0, 1),
    (3, 0.0, 0.0, 4),
    (3, 0.5, 0.25, 2),
    (5, 1.0, 0.5, 8),
    (4, 0.25, 1.0, 3),
]


@pytest.mark.parametrize("cloud", sorted(CLOUDS))
@pytest.mark.parametrize("k,gamma,rho,n_batches", PARAM_GRID)
def test_session_matches_brute_oracle(cloud, k, gamma, rho, n_batches):
    pts = CLOUDS[cloud]()
    res = JoinSession(HybridConfig(
        k=k, m=4, gamma=gamma, rho=rho, n_batches=n_batches,
    )).join(pts)
    want_d, want_i = _brute_oracle(pts, k)
    np.testing.assert_allclose(res.dists, want_d, atol=1e-5)
    # ids must match under distance ties: the distance realized by each
    # chosen id equals the oracle distance at that rank.
    got_d = np.linalg.norm(
        pts[:, None, :] - pts[res.ids], axis=-1
    ).astype(np.float32)
    np.testing.assert_allclose(got_d, want_d, atol=1e-5)
    assert ((res.ids >= 0) & (res.ids < len(pts))).all()
    assert not (res.ids == np.arange(len(pts))[:, None]).any()
    # off-tie ids agree exactly
    ties = np.abs(got_d - want_d) > 0  # float-identical ranks only
    assert ((res.ids == want_i) | ties).all()


# ---------------------------------------------------------------------------
# ρ-floor invariant: rebalancing only ever grows the sparse assignment
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rho", [0.2, 0.5, 0.9])
def test_rebalance_never_starves_sparse_floor(rho):
    pts = _clustered(seed=7)
    res = JoinSession(HybridConfig(
        k=3, m=4, rho=rho, n_batches=4, online_rebalance=True,
    )).join(pts)
    floor = math.ceil(rho * len(pts))
    assert res.stats.n_sparse >= floor
    # every sparse-round query is counted; demotion only adds
    assert res.stats.n_sparse_engine_total >= res.stats.n_sparse
    assert res.stats.n_rebalanced >= 0


def test_queue_rejects_floor_violation():
    with pytest.raises(ValueError, match="floor"):
        queue_lib.run_work_queue(
            npts=10, k=1,
            dense_ids=np.arange(8, dtype=np.int32),
            sparse_ids=np.arange(8, 10, dtype=np.int32),
            home_counts=np.ones(10, np.int64),
            dense_fn=None, sparse_fn=None, brute_fn=None,
            min_sparse=5,
        )


# ---------------------------------------------------------------------------
# WorkQueue mechanics
# ---------------------------------------------------------------------------

def test_workqueue_head_densest_tail_demotes_least_populated():
    home_counts = np.array([5, 50, 7, 90, 2, 30, 60, 11], np.int64)
    ids = np.arange(8, dtype=np.int32)
    q = queue_lib.WorkQueue(ids, home_counts, n_batches=4)
    first = q.next_batch()
    # densest first: home cells 90, 60
    assert list(home_counts[first]) == [90, 60]
    demoted = q.demote(3)
    # least-populated first: 2, 5, 7
    assert list(home_counts[demoted]) == [2, 5, 7]
    # dequeue + demotion never overlap and drain exactly once
    seen = set(first) | set(demoted)
    while q.remaining:
        for i in q.next_batch():
            assert i not in seen
            seen.add(i)
    assert seen == set(range(8))
    assert q.demote(99).size == 0


def test_workqueue_empty_and_single_batch():
    q = queue_lib.WorkQueue(np.zeros((0,), np.int32), np.zeros((0,)), 4)
    assert q.remaining == 0 and q.next_batch().size == 0
    q = queue_lib.WorkQueue(np.arange(5, dtype=np.int32), np.ones(5), 1)
    assert len(q.next_batch()) == 5 and q.remaining == 0


# ---------------------------------------------------------------------------
# scheduler loop with stub engines (deterministic timings)
# ---------------------------------------------------------------------------

def _stub_engines(npts, k, t_dense=1.0, t_sparse_handle=None,
                  fail_ids=(), uncertify_ids=()):
    """Engines that resolve query i to neighbors [i+1..i+k] mod npts and
    report injected timings (so rebalance decisions are deterministic)."""
    fail_ids, uncertify_ids = set(fail_ids), set(uncertify_ids)

    def answer(ids):
        ids = np.asarray(ids)
        nids = (ids[:, None] + np.arange(1, k + 1)[None, :]) % npts
        return np.full((len(ids), k), 0.25, np.float32), nids.astype(np.int32)

    def dense_fn(ids):
        d, i = answer(ids)
        failed = np.array([q in fail_ids for q in ids], bool)
        return d, i, failed, t_dense

    def sparse_fn(ids):
        d, i = answer(ids)
        cert = np.array([q not in uncertify_ids for q in ids], bool)
        handle = queue_lib.AsyncEngineCall((d, i, cert))
        if t_sparse_handle is not None:
            handle.elapsed = t_sparse_handle   # inject T₁
        return handle

    def brute_fn(ids):
        return answer(ids)

    return dense_fn, sparse_fn, brute_fn


def test_scheduler_routes_failures_and_uncertified():
    npts, k = 64, 2
    home = np.arange(npts)
    dense_ids = np.arange(0, 40, dtype=np.int32)
    sparse_ids = np.arange(40, npts, dtype=np.int32)
    dense_fn, sparse_fn, brute_fn = _stub_engines(
        npts, k, fail_ids={3, 7}, uncertify_ids={3, 50})
    fd, fi, src, rep = queue_lib.run_work_queue(
        npts=npts, k=k, dense_ids=dense_ids, sparse_ids=sparse_ids,
        home_counts=home, dense_fn=dense_fn, sparse_fn=sparse_fn,
        brute_fn=brute_fn, n_batches=4, online_rebalance=False)
    assert rep.n_failed == 2
    assert rep.n_uncertified == 2
    assert (fi >= 0).all()
    # failed dense query 3 was uncertified by sparse too -> brute lane
    assert src[3] == 2 and src[50] == 2
    assert src[7] == 1          # failed dense, certified by sparse
    assert src[5] == 0          # clean dense
    assert rep.n_sparse_engine_total == len(sparse_ids) + 2


def test_scheduler_online_demotion_fires_when_sparse_is_cheap():
    """T₂ ≫ T₁ ⇒ ρ^online ≈ 1 ⇒ remaining dense work is demoted from the
    queue tail (paper §V-F applied online)."""
    npts, k = 128, 2
    home = np.arange(npts)          # distinct densities: tail is 0,1,2,...
    dense_ids = np.arange(0, 96, dtype=np.int32)
    sparse_ids = np.arange(96, npts, dtype=np.int32)
    dense_fn, sparse_fn, brute_fn = _stub_engines(
        npts, k, t_dense=10.0, t_sparse_handle=1e-6)
    fd, fi, src, rep = queue_lib.run_work_queue(
        npts=npts, k=k, dense_ids=dense_ids, sparse_ids=sparse_ids,
        home_counts=home, dense_fn=dense_fn, sparse_fn=sparse_fn,
        brute_fn=brute_fn, n_batches=8, online_rebalance=True,
        sync_t1_after=1, demote_quantum=1)
    assert rep.n_rebalanced > 0
    assert rep.rho_online > 0.9
    # demoted queries resolve via the sparse engine (source 1), and they
    # came from the least-populated end of the dense assignment
    demoted = np.nonzero(src[:96] == 1)[0]
    assert len(demoted) == rep.n_rebalanced
    kept_dense = np.nonzero(src[:96] == 0)[0]
    assert home[demoted].max() < home[kept_dense].min()
    assert (fi >= 0).all()


def test_scheduler_no_demotion_when_dense_is_cheap():
    npts, k = 128, 2
    home = np.arange(npts)
    dense_fn, sparse_fn, brute_fn = _stub_engines(
        npts, k, t_dense=1e-6, t_sparse_handle=10.0)
    *_, rep = queue_lib.run_work_queue(
        npts=npts, k=k, dense_ids=np.arange(0, 96, dtype=np.int32),
        sparse_ids=np.arange(96, npts, dtype=np.int32),
        home_counts=home, dense_fn=dense_fn, sparse_fn=sparse_fn,
        brute_fn=brute_fn, n_batches=8, online_rebalance=True)
    assert rep.n_rebalanced == 0


def test_engine_clock_excludes_compiles_in_flight():
    """A sparse round in flight while the host compiles another engine
    times its execution, not the compiler (the engine contract's T₁)."""
    handle = queue_lib.AsyncEngineCall((np.zeros(3),))
    time.sleep(0.2)
    queue_lib.charge_compile(0.2)        # the sleep was a compile
    handle.get()
    assert handle.elapsed < 0.1


def test_cold_query_compiles_first_round_before_the_queue(monkeypatch):
    """The first round's timings seed the online ρ rebalance, so a cold
    query compiles that round's dense and sparse engines before the
    queue dispatches anything — and times it as a warm query would."""
    from repro.runtime import KNNIndex

    pts = _clustered(seed=14)
    index = KNNIndex.build(pts, HybridConfig(k=3, m=4, rho=0.3, n_batches=2))
    seen = {}
    run = queue_lib.run_work_queue

    def spy(**kw):
        seen.update(index.compile_counts)
        return run(**kw)

    monkeypatch.setattr(queue_lib, "run_work_queue", spy)
    index.query(pts + np.float32(0.01))
    assert seen.get("dense", 0) >= 1 and seen.get("sparse", 0) >= 1


# ---------------------------------------------------------------------------
# persistent session: compile-count probe + index reuse
# ---------------------------------------------------------------------------

def test_second_join_triggers_zero_new_engine_compiles():
    pts = _clustered(seed=11)
    # deterministic scheduler (no timing-dependent demotion shapes)
    cfg = HybridConfig(k=3, m=4, gamma=0.3, rho=0.2, n_batches=2,
                       online_rebalance=False)
    session = JoinSession(cfg)
    r1 = session.join(pts)
    total_after_first = session.total_compiles
    r2 = session.join(pts.copy())       # same shapes, fresh values
    assert session.total_compiles == total_after_first
    assert r2.stats.n_engine_compiles == 0
    np.testing.assert_allclose(r1.dists, r2.dists, atol=1e-6)


def test_same_points_object_reuses_index():
    pts = _uniform(seed=12)
    session = JoinSession(HybridConfig(k=2, m=4, n_batches=2))
    r1 = session.join(pts)
    assert r1.stats.t_build > 0
    r2 = session.join(pts)              # identity fast path
    assert r2.stats.t_build == 0.0 and r2.stats.t_select_eps == 0.0
    assert r2.stats.n_engine_compiles == 0
    np.testing.assert_allclose(r1.dists, r2.dists, atol=1e-6)


def test_hybrid_wrapper_delegates_to_session():
    pts = _uniform(seed=13)
    joiner = HybridKNNJoin(HybridConfig(k=2, m=4, n_batches=2))
    res = joiner.join(pts)
    assert joiner.session.total_compiles >= 0
    want_d, _ = _brute_oracle(pts, 2)
    np.testing.assert_allclose(res.dists, want_d, atol=1e-5)
    # new scheduler stats surface through the stable wrapper API
    assert res.stats.n_batches >= 1
    assert len(res.stats.batch_sizes) == res.stats.n_batches
    assert len(res.stats.t_dense_batches) == res.stats.n_batches
