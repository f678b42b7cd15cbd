"""Substrate tests: checkpoint, pipeline, optimizer, runtime, compile
cache."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs.base import SHAPES, get_smoke_config
from repro.data import TokenPipeline
from repro.optim import (
    OptConfig, adamw_update, global_norm, init_opt_state, warmup_cosine,
)
from repro.runtime import (
    StragglerDetector, Supervisor, SupervisorConfig, suggest_rho,
)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def _tree(seed=0):
    r = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(r.normal(size=(8, 4)), jnp.float32),
        "nested": {"b": jnp.asarray(r.integers(0, 9, (3,)), jnp.int32),
                   "c": [jnp.ones((2,)), jnp.zeros((5,), jnp.bfloat16)]},
    }


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    tree = _tree()
    mgr.save(7, tree, extra={"cursor": 42})
    got, extra, step = mgr.restore(tree)
    assert step == 7 and extra == {"cursor": 42}
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tree, got)


def test_checkpoint_async_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    mgr.wait()
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step-"))
    assert len(kept) == 2 and mgr.latest_step() == 4
    got, _, step = mgr.restore(_tree())
    np.testing.assert_array_equal(np.asarray(got["a"]),
                                  np.asarray(_tree(4)["a"]))


def test_checkpoint_detects_corruption(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    tree = _tree()
    mgr.save(1, tree)
    # flip bytes in the arrays file
    d = os.path.join(tmp_path, "step-000000001")
    path = os.path.join(d, "arrays.npz")
    data = dict(np.load(path))
    data["a"] = data["a"] + 1.0
    np.savez(path, **data)
    with pytest.raises(ValueError, match="crc"):
        mgr.restore(tree)


def test_checkpoint_latest_is_hint_not_authority(tmp_path):
    """A crash between the atomic step rename and the LATEST pointer
    update leaves LATEST stale (or pointing at a step that never became
    durable).  latest_step() must warn and fall back to the newest
    durable step instead of trusting the pointer."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, _tree(1))
    mgr.save(2, _tree(2))
    # simulate the pre-LATEST crash: pointer names a missing step
    with open(os.path.join(tmp_path, "LATEST"), "w") as f:
        f.write("step-000000099")
    with pytest.warns(RuntimeWarning, match="falling back to newest durable"):
        assert mgr.latest_step() == 2
    got, _, step = mgr.restore(_tree())
    assert step == 2
    np.testing.assert_array_equal(np.asarray(got["a"]),
                                  np.asarray(_tree(2)["a"]))


def test_checkpoint_rejects_partial_step_with_hint(tmp_path):
    """A step directory missing its manifest (crash mid-write before the
    atomic rename... or a half-copied backup) is not durable: explicit
    restore of it must fail actionably, naming the durable alternatives;
    LATEST pointing at it must fall back."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(3, _tree(3))
    partial = os.path.join(tmp_path, "step-000000007")
    os.makedirs(partial)                          # dir exists, no files
    assert mgr.durable_steps() == [3]
    with pytest.raises(FileNotFoundError,
                       match=r"missing or partial.*durable steps.*\[3\]"):
        mgr.restore(_tree(), step=7)
    with open(os.path.join(tmp_path, "LATEST"), "w") as f:
        f.write("step-000000007")
    with pytest.warns(RuntimeWarning):
        assert mgr.latest_step() == 3


def test_checkpoint_nothing_durable_is_actionable(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    assert mgr.latest_step() is None
    with pytest.raises(FileNotFoundError, match="no durable checkpoint"):
        mgr.restore(_tree())


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore onto a different sharding (here: different device layout is
    simulated by restoring with explicit single-device shardings)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    tree = _tree()
    mgr.save(1, tree)
    shard = NamedSharding(mesh, P())
    got, _, _ = mgr.restore(tree, shardings=shard)
    assert got["a"].sharding == shard


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_resumable():
    cfg = get_smoke_config("olmo_1b")
    shape = SHAPES["train_4k"]
    p1 = TokenPipeline(cfg, shape, batch_override=4, seq_override=32)
    batches = [p1.next_batch() for _ in range(5)]
    # restore from cursor 3 on a "different host"
    p2 = TokenPipeline(cfg, shape, batch_override=4, seq_override=32)
    p2.load_state_dict({"step": 3, "seed": 0})
    b3 = p2.next_batch()
    np.testing.assert_array_equal(np.asarray(batches[3]["tokens"]),
                                  np.asarray(b3["tokens"]))
    # labels are next-token shifted
    np.testing.assert_array_equal(np.asarray(batches[0]["tokens"][:, 1:]),
                                  np.asarray(batches[0]["labels"][:, :-1]))


def test_pipeline_modality_stubs():
    cfg = get_smoke_config("whisper_large_v3")
    p = TokenPipeline(cfg, SHAPES["train_4k"], batch_override=2,
                      seq_override=16)
    b = p.next_batch()
    assert b["frames"].shape == (2, cfg.encoder_seq, cfg.d_model)
    cfg2 = get_smoke_config("llava_next_mistral_7b")
    p2 = TokenPipeline(cfg2, SHAPES["train_4k"], batch_override=2,
                       seq_override=64)
    b2 = p2.next_batch()
    assert b2["patches"].shape == (2, cfg2.n_patches, cfg2.patch_dim)
    assert b2["tokens"].shape[1] == 64 - cfg2.n_patches


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_matches_reference_impl():
    """One step vs a hand-rolled fp64 AdamW."""
    r = np.random.default_rng(0)
    p = r.normal(size=(7,))
    g = r.normal(size=(7,))
    cfg = OptConfig(peak_lr=1e-2, warmup_steps=0, total_steps=10**9,
                    grad_clip=0.0, weight_decay=0.1)
    params = {"w": jnp.asarray(p, jnp.float32)}
    opt = init_opt_state(params, cfg)
    new_p, new_opt, metrics = adamw_update(
        {"w": jnp.asarray(g, jnp.float32)}, opt, params, cfg)
    # reference
    lr = 1e-2
    mu = (1 - cfg.b1) * g
    nu = (1 - cfg.b2) * g * g
    mhat = mu / (1 - cfg.b1)
    vhat = nu / (1 - cfg.b2)
    want = p - lr * (mhat / (np.sqrt(vhat) + cfg.eps) + 0.1 * p)
    np.testing.assert_allclose(np.asarray(new_p["w"]), want, rtol=1e-5)
    assert int(new_opt["count"]) == 1


def test_adamw_grad_clip_and_schedule():
    cfg = OptConfig(peak_lr=1.0, warmup_steps=10, total_steps=100,
                    grad_clip=1.0, end_lr_frac=0.1)
    big = {"w": jnp.full((4,), 100.0)}
    clipped, norm = __import__("repro.optim.adamw", fromlist=["x"]) \
        .clip_by_global_norm(big, 1.0)
    np.testing.assert_allclose(float(global_norm(clipped)), 1.0, rtol=1e-5)
    lr5 = float(warmup_cosine(cfg, jnp.int32(5)))
    lr10 = float(warmup_cosine(cfg, jnp.int32(10)))
    lr100 = float(warmup_cosine(cfg, jnp.int32(100)))
    assert lr5 == pytest.approx(0.5) and lr10 == pytest.approx(1.0)
    assert lr100 == pytest.approx(0.1, rel=1e-3)


def test_bf16_moment_dtype():
    cfg = OptConfig(moment_dtype="bfloat16")
    params = {"w": jnp.ones((3,), jnp.float32)}
    opt = init_opt_state(params, cfg)
    assert opt["mu"]["w"].dtype == jnp.bfloat16
    new_p, new_opt, _ = adamw_update(
        {"w": jnp.ones((3,))}, opt, params, cfg)
    assert new_opt["nu"]["w"].dtype == jnp.bfloat16
    assert new_p["w"].dtype == jnp.float32


# ---------------------------------------------------------------------------
# runtime: stragglers + supervisor
# ---------------------------------------------------------------------------

def test_straggler_detector_flags_persistent_outlier():
    det = StragglerDetector(n_hosts=8)
    r = np.random.default_rng(0)
    flagged = []
    for step in range(20):
        times = 1.0 + 0.01 * r.random(8)
        if step >= 8:
            times[3] = 2.5          # host 3 goes bad
        flagged = det.update(times)
    assert flagged == [3]
    assert 3 not in det.healthy_hosts()


def test_straggler_detector_ignores_transients():
    det = StragglerDetector(n_hosts=4)
    r = np.random.default_rng(1)
    for step in range(20):
        times = 1.0 + 0.01 * r.random(4)
        if step == 10:
            times[2] = 9.0          # single hiccup
        assert det.update(times) == []


def test_suggest_rho_is_eq6():
    assert suggest_rho(2.948e-5, 5.474e-5) == pytest.approx(0.650, abs=1e-3)


def test_supervisor_restarts_from_checkpoint():
    saves = {}
    flags = {"failed": False}

    def save_fn(step, state):
        saves[step] = state

    def restore_fn():
        step = max(saves)
        return saves[step], step

    def step_fn(state, step):
        if step == 7 and not flags["failed"]:
            flags["failed"] = True
            raise RuntimeError("simulated device loss")
        return state + 1

    sup = Supervisor(SupervisorConfig(checkpoint_every=2),
                     save_fn=save_fn, restore_fn=restore_fn)
    state, report = sup.run(0, step_fn, 0, 10)
    assert report.completed and report.restarts == 1
    assert report.final_step == 10
    # state reflects re-executed steps after restore from step 6
    assert state == 10


def test_supervisor_gives_up_on_poison_step():
    def step_fn(state, step):
        raise RuntimeError("always fails")

    sup = Supervisor(SupervisorConfig(max_same_step_failures=2,
                                      max_restarts=10),
                     save_fn=lambda s, st: None,
                     restore_fn=lambda: (0, 0))
    _, report = sup.run(0, step_fn, 0, 5)
    assert not report.completed
    assert len(report.failures) >= 2


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

_CACHE_PROBE = textwrap.dedent("""
    import os, jax, jax.numpy as jnp
    from repro.utils import REPO_ROOT, init_compile_cache
    path = init_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path, path
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        assert path == os.environ["JAX_COMPILATION_CACHE_DIR"], path
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
        assert os.listdir(path), "nothing was cached"
    else:
        assert path == str(REPO_ROOT / ".jax_cache"), path
""")


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_init_compile_cache_location(tmp_path, from_env):
    """The compile cache goes where JAX_COMPILATION_CACHE_DIR says, and
    otherwise to the fixed <repo>/.jax_cache (run in a child process so
    this one's cache settings stay untouched)."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    env["JAX_PLATFORMS"] = "cpu"
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
