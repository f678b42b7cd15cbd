"""Distributed tests — each case runs in a subprocess with 8 fake host
devices (XLA locks the device count at first jax import, so the main
pytest process must keep seeing 1 CPU device)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(body: str, n_devices: int = 8, timeout: int = 600):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


def test_ring_self_join_exact_over_8_shards():
    run_devices("""
        from repro.core import ring_self_join
        mesh = jax.make_mesh((8,), ("data",))
        r = np.random.default_rng(0)
        pts = jnp.asarray(r.normal(size=(512, 16)), jnp.float32)
        fn = ring_self_join(mesh, ("data",), k=4, kernel_mode="ref")
        d, i = jax.block_until_ready(fn(pts))
        d2 = ((pts[:, None] - pts[None]) ** 2).sum(-1)
        d2 = d2.at[jnp.arange(512), jnp.arange(512)].set(jnp.inf)
        want = jnp.sort(d2, axis=1)[:, :4]
        assert float(jnp.abs(d - want).max()) < 1e-4, "ring join inexact"
        assert not (i == jnp.arange(512)[:, None]).any()
    """)


def test_ring_self_join_bf16_wire_near_exact():
    """bf16-wire ring join: same neighbors up to bf16 key precision."""
    run_devices("""
        from repro.core.distributed import ring_self_join_bf16
        from repro.core import ring_self_join
        mesh = jax.make_mesh((8,), ("model",))
        r = np.random.default_rng(7)
        pts = jnp.asarray(r.normal(size=(256, 16)), jnp.float32)
        d32, i32 = jax.block_until_ready(
            ring_self_join(mesh, ("model",), k=4, kernel_mode="ref")(pts))
        d16, i16 = jax.block_until_ready(
            ring_self_join_bf16(mesh, ("model",), k=4)(pts))
        # distances agree to bf16 coordinate precision
        rel = np.abs(np.asarray(d16) - np.asarray(d32)) / \
            np.maximum(np.asarray(d32), 1e-3)
        assert rel.max() < 0.1, rel.max()
        overlap = np.mean([len(set(a) & set(b)) / 4
                           for a, b in zip(np.asarray(i16), np.asarray(i32))])
        assert overlap > 0.9, overlap
    """)


def test_ring_join_chunk_sizes_agree():
    run_devices("""
        from repro.core import ring_self_join
        mesh = jax.make_mesh((4,), ("model",))
        r = np.random.default_rng(8)
        pts = jnp.asarray(r.normal(size=(128, 8)), jnp.float32)
        d1, i1 = jax.block_until_ready(
            ring_self_join(mesh, ("model",), k=3, kernel_mode="ref",
                           corpus_chunk=8)(pts))
        d2, i2 = jax.block_until_ready(
            ring_self_join(mesh, ("model",), k=3, kernel_mode="ref",
                           corpus_chunk=4096)(pts))
        assert np.allclose(np.asarray(d1), np.asarray(d2), rtol=1e-5)
        assert (np.asarray(i1) == np.asarray(i2)).all()
    """)


def test_ring_join_uneven_rows_pow2_padded():
    """|D| not divisible by the shard count: the pow2_bucket row padding
    (shared with the serving path's query-shape buckets) absorbs the
    remainder — padding rows carry id −1 and never win a slot."""
    run_devices("""
        from repro.core import ring_self_join
        mesh = jax.make_mesh((4,), ("data",))
        r = np.random.default_rng(9)
        n = 300                                   # 300 % 4 != 0
        pts = jnp.asarray(r.normal(size=(n, 8)), jnp.float32)
        d, i = jax.block_until_ready(
            ring_self_join(mesh, ("data",), k=3, kernel_mode="ref")(pts))
        assert d.shape == (n, 3) and i.shape == (n, 3)
        d2 = ((pts[:, None] - pts[None]) ** 2).sum(-1)
        d2 = d2.at[jnp.arange(n), jnp.arange(n)].set(jnp.inf)
        want = jnp.sort(d2, axis=1)[:, :3]
        assert float(jnp.abs(d - want).max()) < 1e-4
        assert int(i.min()) >= 0                  # no padding id leaked
    """)


def test_hybrid_spmd_join_resolves_and_is_exact():
    run_devices("""
        from repro.core import hybrid_join_spmd
        mesh = jax.make_mesh((8,), ("data",))
        r = np.random.default_rng(1)
        dense = r.normal(0, 0.05, (384, 8))
        sparse = r.uniform(-3, 3, (128, 8))
        pts = jnp.asarray(np.concatenate([dense, sparse]), jnp.float32)
        fn = hybrid_join_spmd(mesh, ("data",), k=4, rho=0.5, n_levels=3)
        res = jax.block_until_ready(fn(pts, 0.8))
        assert int(res.n_unresolved) == 0
        d2 = ((pts[:, None] - pts[None]) ** 2).sum(-1)
        d2 = d2.at[jnp.arange(512), jnp.arange(512)].set(jnp.inf)
        want = jnp.sort(d2, axis=1)[:, :4]
        ok = res.source != 3
        err = jnp.abs(jnp.where(ok[:, None], res.dists - want, 0.0)).max()
        assert float(err) < 1e-4, f"spmd join inexact: {float(err)}"
    """)


def test_compressed_grad_mean_over_data_axis():
    run_devices("""
        from repro.optim import compressed_grad_mean, init_residuals
        mesh = jax.make_mesh((8,), ("data",))
        r = np.random.default_rng(2)
        g_global = jnp.asarray(r.normal(size=(8, 64)), jnp.float32)

        def local(g, res):
            return compressed_grad_mean({"w": g[0]}, {"w": res[0]}, ("data",))

        fn = jax.jit(jax.shard_map(local, mesh=mesh,
                                   in_specs=(P("data"), P("data")),
                                   out_specs=(P(), P("data")),
                                   check_vma=False))
        mean, new_res = fn(g_global, jnp.zeros((8, 64)))
        want = np.asarray(g_global).mean(axis=0)
        got = np.asarray(mean["w"])
        # int8 wire: error bounded by one quantum of the largest shard
        scale = np.abs(np.asarray(g_global)).max() / 127.0
        assert np.abs(got - want).max() <= scale + 1e-6
    """)


def test_train_step_spmd_on_host_mesh():
    """2×4 mesh: DP×TP train step executes and loss decreases."""
    run_devices("""
        from repro.configs.base import SHAPES, get_smoke_config
        from repro.data import TokenPipeline
        from repro.launch.steps import make_train_step
        from repro.models import init_params
        from repro.optim import OptConfig, init_opt_state
        from repro.sharding import ShardingCtx
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke_config("qwen3_14b")
        shd = ShardingCtx.for_mesh(mesh, seq_shard=False)
        params, specs = init_params(jax.random.PRNGKey(0), cfg)
        opt_cfg = OptConfig(total_steps=10, warmup_steps=1)
        state = {"params": params, "opt": init_opt_state(params, opt_cfg)}
        shardings = shd.param_shardings(params, specs)
        with mesh:
            state["params"] = jax.tree.map(
                lambda x, s: jax.device_put(x, s), params, shardings)
            step = jax.jit(make_train_step(cfg, opt_cfg, shd))
            pipe = TokenPipeline(cfg, SHAPES["train_4k"], batch_override=4,
                                 seq_override=32)
            losses = []
            for _ in range(8):
                state, m = step(state, pipe.next_batch())
                losses.append(float(m["loss"]))
        assert np.isfinite(losses).all()
        assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses
    """)


def test_sharded_knn_lm_lookup():
    run_devices("""
        from repro.models.knn_lm import sharded_lookup
        mesh = jax.make_mesh((8,), ("model",))
        r = np.random.default_rng(3)
        keys = jnp.asarray(r.normal(size=(256, 16)), jnp.float32)
        vals = jnp.asarray(r.integers(0, 100, (256,)), jnp.int32)
        q = jnp.asarray(r.normal(size=(32, 16)), jnp.float32)
        fn = jax.jit(sharded_lookup(mesh, "model", k=4))
        with mesh:
            d, v = jax.block_until_ready(fn(q, keys, vals))
        d2 = ((np.asarray(q)[:, None] - np.asarray(keys)[None]) ** 2).sum(-1)
        idx = np.argsort(d2, axis=1)[:, :4]
        want_d = np.take_along_axis(d2, idx, axis=1)
        np.testing.assert_allclose(np.sort(np.asarray(d), axis=1),
                                   np.sort(want_d, axis=1), rtol=1e-4,
                                   atol=1e-4)
    """)


def test_moe_sharded_dispatch_equivalence():
    """Per-data-shard MoE dispatch (the §Perf collective fix) must equal
    the global-buffer baseline when capacity never binds."""
    run_devices("""
        import dataclasses
        from repro.configs.base import get_smoke_config
        from repro.models import forward_seq, init_params
        from repro.sharding import ShardingCtx
        mesh = jax.make_mesh((8, 1), ("data", "model"))
        cfg = get_smoke_config("granite_moe_1b_a400m")
        hi = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0))
        sh = dataclasses.replace(hi, moe_sharded_dispatch=True)
        params, _ = init_params(jax.random.PRNGKey(0), cfg)
        shd = ShardingCtx.for_mesh(mesh, seq_shard=False)
        r = np.random.default_rng(0)
        toks = jnp.asarray(r.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)
        with mesh:
            h1 = jax.jit(lambda p, t: forward_seq(p, hi, t, shd)[0])(
                params, toks)
            h2 = jax.jit(lambda p, t: forward_seq(p, sh, t, shd)[0])(
                params, toks)
        err = float(jnp.abs(h1 - h2).max())
        assert err < 1e-4, f"sharded dispatch diverges: {err}"
    """)


def test_dryrun_single_cell_end_to_end():
    """The deliverable itself, in miniature: 512-device multi-pod compile
    of a real cell inside the test suite."""
    out = run_devices("""
        import repro.launch.dryrun as dr
        rec = dr.run_cell("granite_moe_1b_a400m", "decode_32k",
                          multi_pod=True, verbose=False)
        assert rec["ok"], rec.get("error")
        assert rec["chips"] == 512
        assert rec["collective_bytes_weighted"]["total"] > 0
        print("MEM", rec["memory_analysis"].get("argument_size_in_bytes"))
    """, n_devices=512, timeout=900)
    assert "MEM" in out
