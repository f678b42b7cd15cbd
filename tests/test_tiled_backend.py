"""Cell-tiled MXU engine backend (ISSUE 2): parity of the tiled path
(`backend="interpret"` — the Pallas kernel body on CPU) against the jnp
ref oracle and the brute baseline, the dot_general lowering guarantee,
and the JoinSession compile probe with the backend cache key."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_mixture
from oracle import oracle_knn
from repro.core import HybridConfig, brute_knn
from repro.core import dense_join as dense_lib
from repro.core import grid as grid_lib
from repro.core import sparse_knn as sparse_lib
from repro.runtime import JoinSession


def _dense_fixture(dim=6, m=4, eps=0.25, seed=1, n_dense=300, n_sparse=100):
    pts = make_mixture(n_dense, n_sparse, dim=dim, seed=seed)
    pts_r = grid_lib.reorder_by_variance(jnp.asarray(pts))[0]
    idx = grid_lib.build_grid(pts_r, jnp.float32(eps), m)
    qids = jnp.arange(len(pts), dtype=jnp.int32)
    return pts_r, idx, qids, jnp.float32(eps)


def _assert_equal_mod_boundary(got, want, pts_r, eps2, tol=1e-4):
    """Per-query ints (found/failed) must match except where the query has
    a candidate within ``tol`` of the ε² cutoff: the ref broadcast-subtract
    and the kernel's ‖q‖²+‖c‖²−2·q·cᵀ round differently at the last ulp, so
    membership of exact-boundary pairs is formulation-dependent."""
    got, want = np.asarray(got), np.asarray(want)
    mism = np.nonzero(got != want)[0]
    if not len(mism):
        return
    pts = np.asarray(pts_r, np.float64)
    d2 = ((pts[mism, None, :] - pts[None, :, :]) ** 2).sum(-1)
    slack = np.abs(d2 - float(eps2)).min(axis=1)
    assert (slack < tol).all(), (
        f"backend mismatch on {len(mism)} queries not explained by ε² "
        f"boundary ties (max slack {slack.max():.3e})"
    )


def _ids_match_mod_ties(pts_r, got_ids, want_ids, mask):
    """ids equal, except where the realized distances tie exactly."""
    pts = np.asarray(pts_r, np.float64)
    q = np.nonzero(mask)[0][:, None]
    gd = ((pts[q] - pts[np.clip(got_ids[mask], 0, len(pts) - 1)]) ** 2).sum(-1)
    wd = ((pts[q] - pts[np.clip(want_ids[mask], 0, len(pts) - 1)]) ** 2).sum(-1)
    same = got_ids[mask] == want_ids[mask]
    pad = (got_ids[mask] < 0) & (want_ids[mask] < 0)
    np.testing.assert_allclose(
        np.where(same | pad, 0.0, gd), np.where(same | pad, 0.0, wd),
        rtol=1e-5, atol=1e-7,
    )


# ---------------------------------------------------------------------------
# dense engine: tiled backend ≡ ref backend over the parity grid
# ---------------------------------------------------------------------------

DENSE_GRID = [
    # (k, budget, block_c, m)
    (1, 1024, 128, 4),
    (5, 1024, 64, 4),
    (4, 4096, 128, 2),
    (3, 2048, 256, 6),
]


@pytest.mark.parametrize("k,budget,block_c,m", DENSE_GRID)
def test_dense_backend_parity(k, budget, block_c, m):
    pts_r, idx, qids, eps = _dense_fixture(m=m)
    ref = dense_lib.dense_join(
        idx, pts_r, qids, eps, k=k, budget=budget, backend="ref")
    til = dense_lib.dense_join(
        idx, pts_r, qids, eps, k=k, budget=budget, block_c=block_c,
        backend="interpret")
    # workload accounting is bit-identical: candidate totals are integer
    # range sums, independent of the distance formulation, and the queue's
    # Eq.-6 rebalance must see the same T₂ proxy regardless of backend
    np.testing.assert_array_equal(
        np.asarray(ref.total_candidates), np.asarray(til.total_candidates))
    # found/failed may differ only on exact ε²-boundary pairs (last-ulp
    # rounding differs between the two distance formulations)
    eps2 = float(eps) ** 2
    _assert_equal_mod_boundary(til.found, ref.found, pts_r, eps2)
    _assert_equal_mod_boundary(til.failed, ref.failed, pts_r, eps2)
    np.testing.assert_allclose(
        np.asarray(ref.dists), np.asarray(til.dists), rtol=1e-4, atol=1e-4)
    _ids_match_mod_ties(
        pts_r, np.asarray(til.ids), np.asarray(ref.ids),
        ~np.asarray(ref.failed))


def test_dense_tiled_matches_brute_on_success():
    """Non-failed tiled results are the exact global KNN (the §V-E
    invariant holds on the tiled path too)."""
    k = 4
    pts_r, idx, qids, eps = _dense_fixture(m=4)
    til = dense_lib.dense_join(
        idx, pts_r, qids, eps, k=k, budget=1024, backend="interpret")
    od, _ = oracle_knn(np.asarray(pts_r), k=k, exclude_self=True,
                       squared=True)
    ok = ~np.asarray(til.failed)
    assert ok.any(), "fixture must produce dense successes"
    np.testing.assert_allclose(
        np.asarray(til.dists)[ok], od[ok], rtol=1e-4, atol=1e-4)


def test_dense_tiled_partial_tile_ignores_padding_neighborhoods():
    """Regression: padding rows (qids = −1) clip to point 0, and point 0's
    3^m neighborhood must NOT be merged into a partial tile's shared
    candidate union — a dense cluster at point 0 would otherwise crowd out
    (or overflow) the real queries' candidates and fail the whole tile."""
    r = np.random.default_rng(0)
    cluster = r.normal(0, 0.01, (300, 4))           # point 0 lives here
    far = r.normal(0, 0.05, (20, 4)) + 5.0          # the actual queries
    pts_r = jnp.asarray(np.concatenate([cluster, far]), jnp.float32)
    eps = jnp.float32(0.5)
    idx = grid_lib.build_grid(pts_r, eps, 4)
    qids = jnp.arange(300, 320, dtype=jnp.int32)    # 20 queries, 108 pad rows
    ref = dense_lib.dense_join(
        idx, pts_r, qids, eps, k=3, budget=128, backend="ref")
    til = dense_lib.dense_join(
        idx, pts_r, qids, eps, k=3, budget=128, backend="interpret")
    assert not np.asarray(ref.failed).any(), "fixture: ref must succeed"
    np.testing.assert_array_equal(
        np.asarray(ref.failed), np.asarray(til.failed))
    np.testing.assert_array_equal(np.asarray(ref.found), np.asarray(til.found))
    np.testing.assert_allclose(
        np.asarray(ref.dists), np.asarray(til.dists), rtol=1e-4, atol=1e-4)


def test_dense_backend_auto_resolves_off_tpu(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert dense_lib.resolve_backend("auto") in ("ref", "fused")
    if jax.default_backend() != "tpu":
        assert dense_lib.resolve_backend("auto") == "ref"
    with pytest.raises(ValueError, match="backend"):
        dense_lib.resolve_backend("cuda")


def test_dense_tiled_lowers_to_dot_general():
    """ISSUE 2 acceptance: the tiled dense hot loop is one tile kernel —
    the ``pairwise_l2`` Pallas call appears in the jaxpr and no
    (B, budget, n) per-query diff tensor is ever materialized (the ref
    path builds exactly that).  The kernel sums L2 from direct
    differences inside the tile (DESIGN.md §11.2), so the MXU
    dot_general this test first required is gone by design."""
    pts_r, idx, qids, eps = _dense_fixture(m=4)
    dim = pts_r.shape[1]
    qb, budget = 128, 1024

    def tiled(pr, q, e):
        return dense_lib.dense_join(
            idx, pr, q, e, k=3, budget=budget, query_block=qb,
            backend="interpret")

    def ref(pr, q, e):
        return dense_lib.dense_join(
            idx, pr, q, e, k=3, budget=budget, query_block=qb, backend="ref")

    tiled_jaxpr = str(jax.make_jaxpr(tiled)(pts_r, qids, eps))
    ref_jaxpr = str(jax.make_jaxpr(ref)(pts_r, qids, eps))
    diff_shape = re.compile(rf"f32\[{qb},\d+,{dim}\]")
    assert "pallas_call" in tiled_jaxpr and "pallas_call" not in ref_jaxpr
    assert not diff_shape.search(tiled_jaxpr), \
        "tiled backend materialized a per-query (B, budget, n) diff tensor"
    # sanity: the pattern does catch the ref path's broadcast-subtract
    assert diff_shape.search(ref_jaxpr)


def test_tile_shared_candidates_is_exact_union():
    """The deduplicated shared block holds exactly the union of the
    tile's per-query candidate sets — no omissions, no repeats."""
    pts_r, idx, qids, eps = _dense_fixture(m=4)
    tiles, _ = grid_lib.group_queries_by_cell(
        idx, jnp.asarray(np.resize(np.asarray(qids), 512), jnp.int32), 128)
    tile = tiles[0]
    safe = jnp.clip(tile, 0, idx.n_points - 1)
    starts, counts = grid_lib.neighbor_ranges(idx, idx.point_coords[safe])
    pos, valid, total, overflow = grid_lib.tile_shared_candidates(
        idx, starts, counts, 4096)
    assert not bool(overflow)
    got = np.asarray(pos)[np.asarray(valid)]
    assert len(got) == int(total)
    assert len(np.unique(got)) == len(got), "duplicate candidate positions"
    want = set()
    s, c = np.asarray(starts), np.asarray(counts)
    for qi in range(s.shape[0]):
        for r in range(s.shape[1]):
            want |= set(range(s[qi, r], s[qi, r] + c[qi, r]))
    assert set(got.tolist()) == want


# ---------------------------------------------------------------------------
# sparse engine: matmul backend ≡ ref backend
# ---------------------------------------------------------------------------

SPARSE_GRID = [(1, 512), (5, 512), (3, 1024)]


@pytest.mark.parametrize("k,budget", SPARSE_GRID)
def test_sparse_backend_parity(k, budget):
    pts = make_mixture(200, 150, dim=8, seed=2)
    pts_r = grid_lib.reorder_by_variance(jnp.asarray(pts))[0]
    pyr = sparse_lib.build_pyramid(pts_r, jnp.float32(0.2), 4)
    qids = jnp.arange(len(pts), dtype=jnp.int32)
    ref = sparse_lib.sparse_knn(
        pyr, pts_r, qids, k=k, budget=budget, backend="ref")
    mm = sparse_lib.sparse_knn(
        pyr, pts_r, qids, k=k, budget=budget, backend="interpret")
    # level/certified may differ only where the pass-1 kth distance sits
    # on a certification boundary (kth vs cert_r(ℓ)² flips with the
    # last-ulp rounding of the distance formulation)
    agree = (
        (np.asarray(ref.level) == np.asarray(mm.level))
        & (np.asarray(ref.certified) == np.asarray(mm.certified))
    )
    if not agree.all():
        cert2 = np.asarray(pyr.cert_radii, np.float64) ** 2
        kth = np.asarray(ref.dists)[~agree, k - 1].astype(np.float64)
        slack = np.abs(kth[:, None] - cert2[None, :]).min(axis=1)
        assert (slack < 1e-4).all(), (
            "sparse backend disagreement not explained by a certification "
            "boundary tie"
        )
    np.testing.assert_array_equal(
        np.asarray(ref.total_candidates)[agree],
        np.asarray(mm.total_candidates)[agree])
    np.testing.assert_allclose(
        np.asarray(ref.dists)[agree], np.asarray(mm.dists)[agree],
        rtol=1e-4, atol=1e-4)
    _ids_match_mod_ties(
        pts_r, np.asarray(mm.ids), np.asarray(ref.ids),
        np.asarray(ref.certified) & agree)


# ---------------------------------------------------------------------------
# session: the backend key keeps the zero-compile steady-state probe
# ---------------------------------------------------------------------------

def test_session_tiled_backend_steady_state_zero_compiles():
    pts = make_mixture(260, 90, dim=6, seed=4)
    # deterministic scheduler (no timing-dependent demotion shapes)
    session = JoinSession(HybridConfig(
        k=3, m=4, gamma=0.3, rho=0.2, backend="interpret",
        online_rebalance=False))
    assert session.backend == "interpret"
    cold = session.join(pts)
    assert cold.stats.n_engine_compiles > 0
    steady = session.join(pts.copy())       # same shapes, fresh values
    assert steady.stats.n_engine_compiles == 0, \
        "backend cache key broke the steady-state zero-compile probe"
    d, _ = brute_knn(
        jnp.asarray(pts), jnp.asarray(pts),
        jnp.arange(len(pts), dtype=jnp.int32), k=3, kernel_mode="ref")
    want = np.sqrt(np.maximum(np.asarray(d), 0.0))
    np.testing.assert_allclose(steady.dists, want, atol=1e-5)


def test_session_backends_do_not_share_cache_entries():
    """ref and tiled sessions on identical shapes must compile separate
    engines (backend is part of the AOT cache key)."""
    pts = make_mixture(200, 56, dim=6, seed=9)
    s_ref = JoinSession(HybridConfig(k=2, m=4, backend="ref"))
    s_ref.join(pts)
    s_til = JoinSession(HybridConfig(k=2, m=4, backend="interpret"))
    r = s_til.join(pts)
    assert r.stats.n_engine_compiles > 0, \
        "tiled session reused the ref session's executables"
