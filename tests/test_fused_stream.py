"""Fused streaming distance+top-K engine (ISSUE 3): kernel-level parity
of `kernels/knn_stream` (interpret mode — the Pallas body on CPU) vs the
ref oracle, `backend="fused"` engine parity against the ref oracle over
the (k, budget, block_c, m) grid including ε²-boundary ties, the
no-materialized-distance-tile jaxpr guarantee, backend resolution (the
REPRO_BACKEND override, resolve-once sessions), and the JoinSession
zero-compile steady-state probe for the fused backend."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_mixture
from oracle import oracle_knn
from test_tiled_backend import (_assert_equal_mod_boundary, _dense_fixture,
                                _ids_match_mod_ties)
from repro.core import HybridConfig, brute_knn
from repro.core import dense_join as dense_lib
from repro.core import grid as grid_lib
from repro.core import sparse_knn as sparse_lib
from repro.kernels.knn_stream import kernel as stream_kernel
from repro.kernels.knn_stream import ops as stream_ops
from repro.kernels.knn_stream import ref as stream_ref
from repro.kernels.knn_topk import kernel as topk_kernel
from repro.kernels.knn_topk import ops as topk_ops
from repro.runtime import JoinSession


# ---------------------------------------------------------------------------
# kernel level: streaming kernel ≡ materialize-then-sort oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q_n,c_n,k,block_q,block_c", [
    (200, 700, 4, 64, 128),     # multi-sub-block streaming + padding
    (64, 128, 1, 64, 128),      # exact tiles, k=1
    (50, 33, 3, 64, 128),       # both operands padded, C < one sub-block
])
def test_stream_kernel_matches_oracle(q_n, c_n, k, block_q, block_c):
    r = np.random.default_rng(q_n + c_n + k)
    q = jnp.asarray(r.normal(size=(q_n, 6)), jnp.float32)
    c = jnp.asarray(r.normal(size=(c_n, 6)), jnp.float32)
    qid = jnp.arange(q_n, dtype=jnp.int32)
    cid = jnp.arange(c_n, dtype=jnp.int32).at[3].set(-1)   # invalid row
    eps2 = jnp.float32(2.0)
    kd0, ki0, f0 = stream_ref.knn_stream_topk_ref(q, c, qid, cid, eps2, k=k)
    kd1, ki1, f1 = stream_ops.knn_stream_topk(
        q, c, qid, cid, eps2, k=k, block_q=block_q, block_c=block_c,
        mode="interpret")
    np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
    np.testing.assert_allclose(
        np.asarray(kd0), np.asarray(kd1), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ki0), np.asarray(ki1))


def test_stream_kernel_excludes_self_pairs():
    r = np.random.default_rng(7)
    pts = jnp.asarray(r.normal(size=(150, 5)), jnp.float32)
    ids = jnp.arange(150, dtype=jnp.int32)
    _, ki, _ = stream_ops.knn_stream_topk(
        pts, pts, ids, ids, jnp.float32(1e9), k=2, block_q=64,
        block_c=64, mode="interpret")
    assert not np.any(np.asarray(ki) == np.arange(150)[:, None])


def test_prefetch_kernel_matches_oracle():
    """Scalar-prefetch kernel ≡ the explicit-gather oracle on an
    arbitrary DMA schedule: random block tables (repeats included) and
    masked aligned ids must land on identical results — the data-driven
    corpus BlockSpec is the only thing under test."""
    r = np.random.default_rng(11)
    block_q, block_c, n_tiles, nblk, n_cb, k = 64, 128, 3, 4, 6, 5
    corpus = jnp.asarray(r.normal(size=(n_cb * block_c, 6)), jnp.float32)
    queries = jnp.asarray(r.normal(size=(n_tiles * block_q, 6)), jnp.float32)
    blk = jnp.asarray(r.integers(0, n_cb, size=(n_tiles, nblk)), jnp.int32)
    rows = np.asarray(blk)[:, :, None] * block_c + np.arange(block_c)
    cand = rows.reshape(n_tiles, -1).astype(np.int32)
    cand[r.random(cand.shape) < 0.3] = -1                   # masked rows
    cand = jnp.asarray(cand)
    qid = jnp.arange(n_tiles * block_q, dtype=jnp.int32)
    eps2 = jnp.float32(4.0)
    kd0, ki0, f0 = stream_ops.knn_stream_topk_prefetch(
        queries, corpus, blk, qid, cand, eps2,
        k=k, block_q=block_q, block_c=block_c, mode="ref")
    kd1, ki1, f1 = stream_ops.knn_stream_topk_prefetch(
        queries, corpus, blk, qid, cand, eps2,
        k=k, block_q=block_q, block_c=block_c, mode="interpret")
    np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
    np.testing.assert_allclose(
        np.asarray(kd0), np.asarray(kd1), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ki0), np.asarray(ki1))


def test_stream_kernel_oversized_k_falls_back_to_ref():
    """k above MAX_UNROLLED_K: the padded kernel refuses loudly, the ops
    wrapper silently takes the ref oracle (mirrors knn_topk policy)."""
    r = np.random.default_rng(3)
    q = jnp.asarray(r.normal(size=(20, 4)), jnp.float32)
    c = jnp.asarray(r.normal(size=(64, 4)), jnp.float32)
    qid = jnp.arange(20, dtype=jnp.int32)
    cid = jnp.arange(64, dtype=jnp.int32)
    big_k = stream_kernel.MAX_UNROLLED_K + 8
    with pytest.raises(ValueError, match="MAX_UNROLLED_K"):
        stream_kernel.knn_stream_topk_padded(
            jnp.zeros((64, 4), jnp.float32), jnp.zeros((64, 4), jnp.float32),
            jnp.zeros((64,), jnp.int32), jnp.zeros((64,), jnp.int32),
            jnp.float32(1.0), k=big_k)
    kd, ki, f = stream_ops.knn_stream_topk(
        q, c, qid, cid, jnp.float32(1e9), k=big_k, mode="interpret")
    kd0, ki0, f0 = stream_ref.knn_stream_topk_ref(
        q, c, qid, cid, jnp.float32(1e9), k=big_k)
    np.testing.assert_allclose(np.asarray(kd), np.asarray(kd0))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f0))


def test_stream_kernel_max_unrolled_k_boundary():
    """The k = MAX_UNROLLED_K vs MAX_UNROLLED_K+1 cliff: the last
    kernel-served k still runs the pallas path, one past it reroutes to
    the ref oracle — both exactly, and the jaxpr proves which engine
    served each side."""
    r = np.random.default_rng(9)
    q = jnp.asarray(r.normal(size=(40, 4)), jnp.float32)
    c = jnp.asarray(r.normal(size=(80, 4)), jnp.float32)
    qid = jnp.arange(40, dtype=jnp.int32)
    cid = jnp.arange(80, dtype=jnp.int32)
    eps2 = jnp.float32(1e9)
    kmax = stream_kernel.MAX_UNROLLED_K

    def jaxpr_for(k):
        return str(jax.make_jaxpr(
            lambda a, b: stream_ops.knn_stream_topk(
                a, b, qid, cid, eps2, k=k, mode="interpret"))(q, c))

    assert "pallas_call" in jaxpr_for(kmax)
    assert "pallas_call" not in jaxpr_for(kmax + 1)
    for k in (kmax, kmax + 1):
        kd, ki, f = stream_ops.knn_stream_topk(
            q, c, qid, cid, eps2, k=k, mode="interpret")
        kd0, ki0, f0 = stream_ref.knn_stream_topk_ref(
            q, c, qid, cid, eps2, k=k)
        np.testing.assert_allclose(
            np.asarray(kd), np.asarray(kd0), rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(ki), np.asarray(ki0))
        np.testing.assert_array_equal(np.asarray(f), np.asarray(f0))


def test_oversized_k_fallback_logs_once(monkeypatch, caplog):
    """The oversized-k reroute to the ref oracle logs exactly one
    warning per process — visible the first time, silent on every later
    trace (the cliff used to be silent)."""
    monkeypatch.setattr(topk_ops, "_oversized_k_warned", set())
    r = np.random.default_rng(4)
    qid = jnp.arange(16, dtype=jnp.int32)
    big_k = stream_kernel.MAX_UNROLLED_K + 1
    with caplog.at_level("WARNING", logger="repro.kernels.knn_stream.ops"):
        for n_c in (48, 56):   # two shapes → two traces, one line
            q = jnp.asarray(r.normal(size=(16, 4)), jnp.float32)
            c = jnp.asarray(r.normal(size=(n_c, 4)), jnp.float32)
            stream_ops.knn_stream_topk(
                q, c, qid, jnp.arange(n_c, dtype=jnp.int32),
                jnp.float32(1e9), k=big_k, mode="interpret")
    hits = [rec for rec in caplog.records if "MAX_UNROLLED_K" in rec.message]
    assert len(hits) == 1, [rec.message for rec in caplog.records]
    # a mode that never wanted the kernel (explicit ref) stays silent
    caplog.clear()
    monkeypatch.setattr(topk_ops, "_oversized_k_warned", set())
    with caplog.at_level("WARNING", logger="repro.kernels.knn_stream.ops"):
        q = jnp.asarray(r.normal(size=(16, 4)), jnp.float32)
        c = jnp.asarray(r.normal(size=(40, 4)), jnp.float32)
        stream_ops.knn_stream_topk(
            q, c, qid, jnp.arange(40, dtype=jnp.int32),
            jnp.float32(1e9), k=big_k, mode="ref")
    assert not [r2 for r2 in caplog.records if "MAX_UNROLLED_K" in r2.message]


def test_knn_topk_oversized_k_logs_once_at_boundary(monkeypatch, caplog):
    """The brute lane's tile kernel shares the log-once helper: k =
    MAX_UNROLLED_K runs the Pallas kernel silently, k = MAX_UNROLLED_K+1
    reroutes to the jnp reference with exactly one warning across
    traces, and both sides stay exact."""
    monkeypatch.setattr(topk_ops, "_oversized_k_warned", set())
    r = np.random.default_rng(5)
    kmax = topk_kernel.MAX_UNROLLED_K
    q = jnp.asarray(r.normal(size=(24, 4)), jnp.float32)
    qid = jnp.arange(24, dtype=jnp.int32)

    def run(k, n_c):
        c = jnp.asarray(r.normal(size=(n_c, 4)), jnp.float32)
        cid = jnp.arange(n_c, dtype=jnp.int32)
        kd, ki = topk_ops.knn_topk(q, c, qid, cid, k=k, mode="interpret")
        wd, wi = oracle_knn(c, q, k=k, squared=True, exclude_self=True)
        np.testing.assert_allclose(np.asarray(kd), wd, rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(ki), wi)

    with caplog.at_level("WARNING", logger="repro.kernels.knn_topk.ops"):
        run(kmax, 72)
        assert not [x for x in caplog.records
                    if "MAX_UNROLLED_K" in x.message]
        for n_c in (72, 80):   # two shapes → two traces, one line
            run(kmax + 1, n_c)
    hits = [x for x in caplog.records if "MAX_UNROLLED_K" in x.message]
    assert len(hits) == 1, [x.message for x in caplog.records]
    assert hits[0].name == "repro.kernels.knn_topk.ops"
    assert "knn_topk" in hits[0].message


# ---------------------------------------------------------------------------
# dense engine: fused backend ≡ ref backend over the parity grid
# ---------------------------------------------------------------------------

FUSED_GRID = [
    # (k, budget, block_c, m)
    (1, 1024, 128, 4),
    (5, 1024, 64, 4),
    (4, 4096, 128, 2),
    (3, 2048, 256, 6),
]


@pytest.mark.parametrize("k,budget,block_c,m", FUSED_GRID)
def test_dense_fused_backend_parity(k, budget, block_c, m):
    pts_r, idx, qids, eps = _dense_fixture(m=m)
    ref = dense_lib.dense_join(
        idx, pts_r, qids, eps, k=k, budget=budget, backend="ref")
    fus = dense_lib.dense_join(
        idx, pts_r, qids, eps, k=k, budget=budget, block_c=block_c,
        backend="fused")
    # workload accounting bit-identical (integer range sums)
    np.testing.assert_array_equal(
        np.asarray(ref.total_candidates), np.asarray(fus.total_candidates))
    # found/failed bit-compatible modulo exact ε²-boundary ties (last-ulp
    # rounding differs between broadcast-subtract and the matmul identity)
    eps2 = float(eps) ** 2
    _assert_equal_mod_boundary(fus.found, ref.found, pts_r, eps2)
    _assert_equal_mod_boundary(fus.failed, ref.failed, pts_r, eps2)
    np.testing.assert_allclose(
        np.asarray(ref.dists), np.asarray(fus.dists), rtol=1e-4, atol=1e-4)
    _ids_match_mod_ties(
        pts_r, np.asarray(fus.ids), np.asarray(ref.ids),
        ~np.asarray(ref.failed))


def test_dense_fused_eps_boundary_ties():
    """Points spaced exactly ε apart: every adjacent pair sits ON the ε²
    cutoff, the adversarial case for a one-pass ε filter.  Distances and
    workload must still agree; found may differ only by boundary-pair
    membership (the documented last-ulp formulation difference)."""
    eps = 0.5
    xs = np.arange(40, dtype=np.float32) * np.float32(eps)
    pts = np.zeros((40, 4), np.float32)
    pts[:, 0] = xs
    # tiny variance in the other dims so reorder/build are well-posed
    pts[:, 1:] = np.random.default_rng(0).normal(0, 1e-3, (40, 3))
    pts_r = jnp.asarray(pts)
    idx = grid_lib.build_grid(pts_r, jnp.float32(eps), 2)
    qids = jnp.arange(40, dtype=jnp.int32)
    ref = dense_lib.dense_join(
        idx, pts_r, qids, jnp.float32(eps), k=2, budget=256, backend="ref")
    fus = dense_lib.dense_join(
        idx, pts_r, qids, jnp.float32(eps), k=2, budget=256, backend="fused")
    np.testing.assert_array_equal(
        np.asarray(ref.total_candidates), np.asarray(fus.total_candidates))
    _assert_equal_mod_boundary(
        fus.found, ref.found, pts_r, eps * eps, tol=1e-3)


def test_dense_fused_matches_brute_on_success():
    """§V-E invariant on the streaming path: non-failed fused results
    are the exact global KNN."""
    k = 4
    pts_r, idx, qids, eps = _dense_fixture(m=4)
    fus = dense_lib.dense_join(
        idx, pts_r, qids, eps, k=k, budget=1024, backend="fused")
    od, _ = oracle_knn(np.asarray(pts_r), k=k, exclude_self=True,
                       squared=True)
    ok = ~np.asarray(fus.failed)
    assert ok.any(), "fixture must produce dense successes"
    np.testing.assert_allclose(
        np.asarray(fus.dists)[ok], od[ok], rtol=1e-4, atol=1e-4)


def test_dense_fused_no_materialized_distance_tile():
    """ISSUE 3 acceptance: the fused path's jaxpr holds NO (block,
    budget) f32 distance tile — the two-pass tiled path materializes
    exactly that as its pallas output (positive control), the streaming
    path only ever touches (block, block_c) sub-tiles in VMEM."""
    pts_r, idx, qids, eps = _dense_fixture(m=4)
    dim = pts_r.shape[1]
    qb, budget, block_c = 128, 1024, 128

    def run(backend):
        def f(pr, q, e):
            return dense_lib.dense_join(
                idx, pr, q, e, k=3, budget=budget, query_block=qb,
                block_c=block_c, backend=backend)
        return str(jax.make_jaxpr(f)(pts_r, qids, eps))

    fused_jaxpr = run("fused")
    tiled_jaxpr = run("interpret")
    tile_shape = re.compile(rf"f32\[{qb},{budget}\]")
    diff_shape = re.compile(rf"f32\[{qb},\d+,{dim}\]")
    assert tile_shape.search(tiled_jaxpr), \
        "positive control: two-pass tiled path must materialize the tile"
    assert not tile_shape.search(fused_jaxpr), \
        "fused backend materialized a (block, budget) distance tile"
    assert not diff_shape.search(fused_jaxpr), \
        "fused backend materialized a per-query (B, budget, n) diff tensor"
    # the streaming kernel is present and fed by the shared-candidate path
    assert "knn_stream" in fused_jaxpr or "pallas_call" in fused_jaxpr


def test_dense_fused_no_gathered_candidate_copy():
    """ISSUE 10 acceptance: the scalar-prefetch path DMAs corpus blocks
    straight from HBM inside the kernel — its jaxpr holds NO gathered
    (budget, dim) / (tiles, budget, dim) f32 candidate copy. The legacy
    gather engine (still serving oversized k) materializes exactly that
    operand, giving the positive control for the regex."""
    pts_r, idx, qids, eps = _dense_fixture(m=4)
    dim = pts_r.shape[1]
    qb, budget, block_c = 128, 1024, 128
    # the padded corpus is 512 rows here, so f32[...,1024,6] can only be
    # a gathered candidate operand — keep the regex unambiguous
    assert pts_r.shape[0] <= 512 < budget

    def run(k):
        def f(pr, q, e):
            return dense_lib.dense_join(
                idx, pr, q, e, k=k, budget=budget, query_block=qb,
                block_c=block_c, backend="fused")
        return str(jax.make_jaxpr(f)(pts_r, qids, eps))

    prefetch_jaxpr = run(3)
    legacy_jaxpr = run(stream_kernel.MAX_UNROLLED_K + 1)
    gathered = re.compile(rf"f32\[(?:\d+,)?{budget},{dim}\]")
    assert gathered.search(legacy_jaxpr), \
        "positive control: the legacy fused path must gather candidates"
    assert not gathered.search(prefetch_jaxpr), \
        "prefetch fused path materialized a gathered candidate copy"
    assert "pallas_call" in prefetch_jaxpr


# ---------------------------------------------------------------------------
# sparse engine: fused streaming scan ≡ ref backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,budget", [(1, 512), (5, 512), (3, 1024)])
def test_sparse_fused_backend_parity(k, budget):
    pts = make_mixture(200, 150, dim=8, seed=2)
    pts_r = grid_lib.reorder_by_variance(jnp.asarray(pts))[0]
    pyr = sparse_lib.build_pyramid(pts_r, jnp.float32(0.2), 4)
    qids = jnp.arange(len(pts), dtype=jnp.int32)
    ref = sparse_lib.sparse_knn(
        pyr, pts_r, qids, k=k, budget=budget, backend="ref")
    fus = sparse_lib.sparse_knn(
        pyr, pts_r, qids, k=k, budget=budget, backend="fused")
    agree = (
        (np.asarray(ref.level) == np.asarray(fus.level))
        & (np.asarray(ref.certified) == np.asarray(fus.certified))
    )
    if not agree.all():
        cert2 = np.asarray(pyr.cert_radii, np.float64) ** 2
        kth = np.asarray(ref.dists)[~agree, k - 1].astype(np.float64)
        slack = np.abs(kth[:, None] - cert2[None, :]).min(axis=1)
        assert (slack < 1e-4).all(), (
            "fused sparse disagreement not explained by a certification "
            "boundary tie")
    np.testing.assert_array_equal(
        np.asarray(ref.total_candidates)[agree],
        np.asarray(fus.total_candidates)[agree])
    np.testing.assert_allclose(
        np.asarray(ref.dists)[agree], np.asarray(fus.dists)[agree],
        rtol=1e-4, atol=1e-4)
    _ids_match_mod_ties(
        pts_r, np.asarray(fus.ids), np.asarray(ref.ids),
        np.asarray(ref.certified) & agree)


def test_sparse_fused_no_full_budget_gather():
    """The streaming scan never materializes the (B, budget, n) gathered
    operand nor a (B, budget) distance tile — only per-chunk slices."""
    pts = make_mixture(120, 80, dim=6, seed=5)
    pts_r = grid_lib.reorder_by_variance(jnp.asarray(pts))[0]
    pyr = sparse_lib.build_pyramid(pts_r, jnp.float32(0.2), 4)
    qids = jnp.arange(len(pts), dtype=jnp.int32)
    budget, qb, dim = 512, 128, pts_r.shape[1]
    assert budget > sparse_lib.STREAM_CHUNK

    def f(pr, q):
        return sparse_lib.sparse_knn(
            pyr, pr, q, k=3, budget=budget, query_block=qb, backend="fused")

    jaxpr = str(jax.make_jaxpr(f)(pts_r, qids))
    assert not re.search(rf"f32\[{qb},{budget},{dim}\]", jaxpr), \
        "fused sparse path gathered the full (B, budget, n) operand"
    assert not re.search(rf"f32\[{qb},{budget}\]", jaxpr), \
        "fused sparse path materialized a (B, budget) distance tile"


# ---------------------------------------------------------------------------
# backend resolution: REPRO_BACKEND override, resolve-once sessions
# ---------------------------------------------------------------------------

def test_repro_backend_env_overrides_auto(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "interpret")
    assert dense_lib.resolve_backend("auto") == "interpret"
    # explicit backends always win over the env
    assert dense_lib.resolve_backend("ref") == "ref"
    assert dense_lib.resolve_backend("fused") == "fused"
    monkeypatch.setenv("REPRO_BACKEND", "auto")
    with pytest.raises(ValueError, match="REPRO_BACKEND"):
        dense_lib.resolve_backend("auto")
    monkeypatch.setenv("REPRO_BACKEND", "cuda")
    with pytest.raises(ValueError, match="REPRO_BACKEND"):
        dense_lib.resolve_backend("auto")


def test_session_resolves_backend_once(monkeypatch):
    """The session captures the env-overridden resolution at
    construction; later env changes must not re-resolve mid-session."""
    monkeypatch.setenv("REPRO_BACKEND", "ref")
    session = JoinSession(HybridConfig(k=2, m=4))
    assert session.backend == "ref"
    monkeypatch.setenv("REPRO_BACKEND", "interpret")
    assert session.backend == "ref"


# ---------------------------------------------------------------------------
# session: fused backend keeps the zero-compile steady-state probe
# ---------------------------------------------------------------------------

def test_session_fused_backend_steady_state_zero_compiles():
    pts = make_mixture(260, 90, dim=6, seed=4)
    session = JoinSession(HybridConfig(
        k=3, m=4, gamma=0.3, rho=0.2, backend="fused",
        online_rebalance=False))
    assert session.backend == "fused"
    cold = session.join(pts)
    assert cold.stats.n_engine_compiles > 0
    steady = session.join(pts.copy())       # same shapes, fresh values
    assert steady.stats.n_engine_compiles == 0, \
        "fused backend broke the steady-state zero-compile probe"
    d, _ = brute_knn(
        jnp.asarray(pts), jnp.asarray(pts),
        jnp.arange(len(pts), dtype=jnp.int32), k=3, kernel_mode="ref")
    want = np.sqrt(np.maximum(np.asarray(d), 0.0))
    np.testing.assert_allclose(steady.dists, want, atol=1e-5)
    # the memory-analysis probe reports per engine (None where the
    # platform's Compiled.memory_analysis() is unavailable)
    mem = session.memory_analysis()
    assert set(mem) <= {"dense", "sparse", "brute"}
    assert "dense" in mem
