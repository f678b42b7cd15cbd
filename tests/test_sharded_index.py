"""Sharded-index parity (ISSUE 5 acceptance): `KNNIndex.build(mesh=...)`
on a 4-fake-device mesh must match the single-device `KNNIndex` oracle
bit-for-bit on ids (and to float ulps on distances) for self-joins and
R≠S batches across k/backend/m, dedup duplicated pad rows on uneven
|D|, agree between merge strategies, and compile zero new engines for
same-bucket steady-state queries on every mesh shape.

Each case runs in a subprocess with its own fake-device count (XLA
locks the device count at first jax import, so the main pytest process
must keep seeing 1 CPU device)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Shared preamble: mixture database (dense cores + sparse background so
# both engines get real work), foreign batch, float64 oracle, and the
# sharded-vs-single parity assertion.
PREAMBLE = """
    from repro.core import HybridConfig
    from repro.runtime import KNNIndex, ShardedKNNIndex
    from repro.launch.mesh import make_serving_mesh

    def make_db(seed=0, n_core=300, n_bg=140, dim=6):
        r = np.random.default_rng(seed)
        core = (0.05 * r.normal(size=(n_core, dim))).astype(np.float32)
        bg = r.uniform(-3.0, 3.0, (n_bg, dim)).astype(np.float32)
        return np.concatenate([core, bg]).astype(np.float32)

    def make_queries(seed=1, n=97, dim=6):
        r = np.random.default_rng(seed)
        near = (0.05 * r.normal(size=(n - n // 3, dim))).astype(np.float32)
        far = r.uniform(3.0, 6.0, (n // 3, dim)).astype(np.float32)
        return np.concatenate([near, far]).astype(np.float32)

    from oracle import oracle_knn

    def oracle64(refs, queries, k, mask_diag=False):
        # Shared float64 oracle (tests/oracle.py); dists only here.
        return oracle_knn(refs, queries, k=k, exclude_self=mask_diag)[0]

    def assert_parity(sharded_res, single_res, refs, queries, k,
                      mask_diag=False):
        # Sharded vs the single-device KNNIndex oracle: identical
        # neighbor ids; distances computed by the same engine
        # formulation per pair, so equal to within a last-ulp
        # dense/sparse/brute formulation difference.
        np.testing.assert_array_equal(sharded_res.ids, single_res.ids)
        np.testing.assert_allclose(sharded_res.dists, single_res.dists,
                                   rtol=2e-6, atol=2e-6)
        # ...and both against the float64 materialized oracle.
        want = oracle64(refs, queries, k, mask_diag=mask_diag)
        np.testing.assert_allclose(np.sort(sharded_res.dists, 1), want,
                                   atol=1e-4)
        assert ((sharded_res.ids >= 0)
                & (sharded_res.ids < len(refs))).all()
        for row in sharded_res.ids:
            real = row[row >= 0]
            assert len(set(real.tolist())) == len(real), "duplicate ids"
"""


def run_devices(body: str, n_devices: int = 4, timeout: int = 900):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        import jax
        import jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(PREAMBLE) + textwrap.dedent(body)
    # tests/ on the path too: the preamble imports the shared oracle.
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


# ---------------------------------------------------------------------------
# Parity vs the single-device oracle over k / backend / m
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,k,m", [
    ("ref", 1, 2),
    ("ref", 5, 4),
    ("ref", 3, 6),
    ("interpret", 3, 4),
    ("fused", 3, 4),
])
def test_sharded_query_matches_single_device(backend, k, m):
    """Self-join AND R≠S on 4 shards ≡ the single-device pipeline."""
    run_devices(f"""
        db = make_db(seed=10 + {k})
        q = make_queries(seed=20 + {k})
        cfg = HybridConfig(k={k}, m={m}, gamma=0.3, rho=0.15, n_batches=2,
                           backend="{backend}", online_rebalance=False)
        mesh = make_serving_mesh(4)
        sharded = KNNIndex.build(db, cfg, mesh=mesh)
        assert isinstance(sharded, ShardedKNNIndex)
        single = KNNIndex.build(db, cfg)

        assert_parity(sharded.query(q), single.query(q), db, q, {k})
        assert_parity(sharded.query(exclude_self=True),
                      single.query(exclude_self=True),
                      db, db, {k}, mask_diag=True)
    """)


def test_uneven_db_pads_and_dedups():
    """|D| % P ≠ 0: pad rows duplicate a resident point per shard; the
    collective merge must suppress the repeated global ids."""
    run_devices("""
        db = make_db(seed=3, n_core=300, n_bg=137)      # 437 over 4
        q = make_queries(seed=4)
        cfg = HybridConfig(k=4, m=4, gamma=0.3, rho=0.15, n_batches=2,
                           backend="ref", online_rebalance=False)
        mesh = make_serving_mesh(4)
        sharded = KNNIndex.build(db, cfg, mesh=mesh)
        assert sharded.n_pad == 3 and sharded.shard_n == 110
        single = KNNIndex.build(db, cfg)
        assert_parity(sharded.query(q), single.query(q), db, q, 4)
        assert_parity(sharded.query(exclude_self=True),
                      single.query(exclude_self=True),
                      db, db, 4, mask_diag=True)

        # fewer points than shards: a clear guard, not a shape error
        tiny = db[:3]
        try:
            KNNIndex.build(tiny, HybridConfig(k=1, m=4), mesh=mesh)
            raise SystemExit("tiny cloud sharded without complaint")
        except AssertionError as e:
            assert "shard" in str(e), e
    """)


def test_merge_strategies_agree():
    """all-gather fold and ppermute tree-merge produce identical output
    (and "auto" resolves per the documented crossover)."""
    run_devices("""
        from repro.core.distributed import merge_strategy
        assert merge_strategy(4, "auto") == "allgather"
        assert merge_strategy(8, "auto") == "tree"
        assert merge_strategy(6, "auto") == "allgather"  # not pow2
        try:
            merge_strategy(6, "tree")
            raise SystemExit("tree accepted non-pow2 shard count")
        except ValueError:
            pass

        db = make_db(seed=5)
        q = make_queries(seed=6)
        cfg = HybridConfig(k=3, m=4, gamma=0.3, rho=0.15, n_batches=2,
                           backend="ref", online_rebalance=False)
        mesh = make_serving_mesh(4)
        ag = ShardedKNNIndex.build(db, cfg, mesh=mesh, merge="allgather")
        tr = ShardedKNNIndex.build(db, cfg, mesh=mesh, merge="tree")
        ra, rt = ag.query(q), tr.query(q)
        np.testing.assert_array_equal(ra.ids, rt.ids)
        np.testing.assert_array_equal(ra.dists, rt.dists)
    """)


# ---------------------------------------------------------------------------
# Serving: zero-compile steady state per mesh shape
# ---------------------------------------------------------------------------

def test_zero_compile_steady_state_per_mesh_shape():
    """Same-bucket repeat queries on a sharded index must compile zero
    new engines — including the collective merge — on every mesh shape
    (the merge compiles exactly once per (shape-bucket, k))."""
    run_devices("""
        db = make_db(seed=7, n_core=280, n_bg=120)
        q = make_queries(seed=8, n=120)
        cfg = HybridConfig(k=3, m=4, gamma=0.3, rho=0.15, n_batches=2,
                           backend="ref", online_rebalance=False)
        for n_shards in (1, 2, 4):
            mesh = make_serving_mesh(n_shards)
            index = KNNIndex.build(db, cfg, mesh=mesh)
            cold = index.query(q)
            assert cold.stats.n_engine_compiles > 0
            assert index.compile_counts["merge"] == 1, index.compile_counts
            warm = index.query(q.copy())             # same bucket, new values
            assert warm.stats.n_engine_compiles == 0, (
                n_shards, index.compile_counts)
            np.testing.assert_array_equal(cold.ids, warm.ids)
            # self-join path steady state too
            index.query(exclude_self=True)
            again = index.query(exclude_self=True)
            assert again.stats.n_engine_compiles == 0, n_shards
    """)


def test_shards_run_on_their_own_devices():
    """Each shard's points, grid and pyramid are committed to a distinct
    device of the mesh, and its engines compile for that device — not
    every shard on the default device."""
    run_devices("""
        from repro.runtime.knn_index import _ENGINE_CACHE
        db = make_db(seed=11)
        cfg = HybridConfig(k=3, m=4, backend="ref")
        index = KNNIndex.build(db, cfg, mesh=make_serving_mesh(4))
        owners = []
        for shard in index.shards:
            leaves = jax.tree_util.tree_leaves(
                (shard.points_r, shard.grid, shard.pyramid))
            devs = {d for leaf in leaves for d in leaf.sharding.device_set}
            assert len(devs) == 1, devs
            owners.append(devs.pop())
        assert len(set(owners)) == 4, owners
        index.query(make_queries(seed=12))
        engine_devs = {
            frozenset(sh.device_set)
            for key, ex in _ENGINE_CACHE.items() if key[0] != "merge"
            for sh in jax.tree_util.tree_leaves(ex.input_shardings)}
        for d in owners:
            assert frozenset([d]) in engine_devs, (d, engine_devs)
    """)


def test_session_mesh_plumbing():
    """JoinSession(mesh=...) owns a sharded index: join() is the sharded
    self-join, index_for() serves R≠S, counters are shared."""
    run_devices("""
        from repro.runtime import JoinSession
        db = make_db(seed=9)
        q = make_queries(seed=11, n=64)
        cfg = HybridConfig(k=2, m=4, n_batches=2, backend="ref",
                           online_rebalance=False)
        mesh = make_serving_mesh(4)
        sess = JoinSession(cfg, mesh=mesh)
        res = sess.join(db)
        single = KNNIndex.build(db, cfg).query(exclude_self=True)
        np.testing.assert_array_equal(res.ids, single.ids)
        index = sess.index_for(db)
        assert isinstance(index, ShardedKNNIndex)
        assert index is sess.index_for(db)           # object-identity reuse
        rq = index.query(q)
        want = oracle64(db, q, 2)
        np.testing.assert_allclose(np.sort(rq.dists, 1), want, atol=1e-4)
        assert sess.total_compiles == index.total_compiles
        assert "merge" in sess.compile_counts
    """)


def test_spmd_join_routes_through_splitter():
    """hybrid_join_spmd's ρ split IS splitter.split_from_counts: with a
    generous budget (no dense failures) the dense-resolved set equals
    the splitter's to_dense prediction on each device's local queries,
    and rho=1.0 forces everything off the dense engine."""
    run_devices("""
        from repro.core import hybrid_join_spmd
        from repro.core import splitter as split_lib
        from repro.core import grid as grid_lib

        mesh = make_serving_mesh(4, axis="data")
        db = make_db(seed=12, n_core=384, n_bg=128)   # 512 over 4
        pts = jnp.asarray(db)
        k, m, gamma, rho, eps = 4, 6, 0.2, 0.25, 0.8

        fn = hybrid_join_spmd(mesh, ("data",), k=k, m=m, rho=rho,
                              gamma=gamma, dense_budget=4096, n_levels=3)
        res = jax.block_until_ready(fn(pts, eps))
        assert int(res.n_unresolved) == 0

        # Host-side prediction: the corpus is replicated, so each
        # device's grid equals the global one; queries shard as
        # contiguous arange ranges.
        index = grid_lib.build_grid(pts, jnp.float32(eps), m)
        home_all = np.asarray(index.cell_counts[index.point_cell_pos])
        src = np.asarray(res.source)
        q_loc = len(db) // 4
        for d in range(4):
            rows = slice(d * q_loc, (d + 1) * q_loc)
            split = split_lib.split_from_counts(
                jnp.asarray(home_all[rows]), k, m, gamma, rho)
            want_dense = np.asarray(split.to_dense)
            np.testing.assert_array_equal(src[rows] == 0, want_dense)

        # rho=1.0: the ρ floor demotes every query off the dense engine.
        fn1 = hybrid_join_spmd(mesh, ("data",), k=k, m=m, rho=1.0,
                               gamma=gamma, n_levels=3)
        res1 = jax.block_until_ready(fn1(pts, eps))
        assert int(res1.n_unresolved) == 0
        assert not (np.asarray(res1.source) == 0).any()

        # And the join stays exact either way.
        d2 = ((db[:, None].astype(np.float64)
               - db[None].astype(np.float64)) ** 2).sum(-1)
        np.fill_diagonal(d2, np.inf)
        want = np.sort(d2, axis=1)[:, :k]
        for r in (res, res1):
            err = np.abs(np.where((np.asarray(r.source) != 3)[:, None],
                                  np.asarray(r.dists) - want, 0.0)).max()
            assert err < 1e-3, err
    """)
