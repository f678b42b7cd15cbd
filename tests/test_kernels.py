"""Per-kernel validation: Pallas (interpret mode — the kernel body runs
on CPU) vs the pure-jnp ref oracle, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bin_hist import ops as bh_ops, ref as bh_ref
from repro.kernels.knn_topk import ops as kt_ops, ref as kt_ref
from repro.kernels.pairwise_l2 import ops as pl_ops, ref as pl_ref

SHAPES = [(8, 16, 4), (64, 192, 24), (100, 300, 7), (128, 256, 128),
          (33, 513, 65)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _data(q, c, d, dtype, seed=0):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(q, d)), dtype),
            jnp.asarray(r.normal(size=(c, d)), dtype))


@pytest.mark.parametrize("q,c,d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pairwise_l2_matches_ref(q, c, d, dtype):
    qa, ca = _data(q, c, d, dtype)
    got = pl_ops.pairwise_sq_l2(qa, ca, mode="interpret")
    want = pl_ref.pairwise_sq_l2_ref(qa, ca)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("q,c,d", SHAPES)
@pytest.mark.parametrize("k", [1, 5, 8])
def test_knn_topk_matches_ref(q, c, d, k):
    qa, ca = _data(q, c, d, jnp.float32)
    qids = jnp.arange(q, dtype=jnp.int32)
    cids = jnp.arange(c, dtype=jnp.int32)
    gd, gi = kt_ops.knn_topk(qa, ca, qids, cids, k=k, mode="interpret")
    wd, wi = kt_ref.knn_topk_ref(qa, ca, qids, cids, k=k)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(gi) == np.asarray(wi)).all()


def test_knn_topk_self_exclusion_and_padding():
    qa, ca = _data(32, 64, 8, jnp.float32)
    # queries ARE the first 32 candidates; ids collide -> self excluded
    qids = jnp.arange(32, dtype=jnp.int32)
    cids = jnp.arange(64, dtype=jnp.int32)
    ca = ca.at[:32].set(qa)
    gd, gi = kt_ops.knn_topk(qa, ca, qids, cids, k=4, mode="interpret")
    assert not (np.asarray(gi) == np.arange(32)[:, None]).any()
    assert (np.asarray(gd) > 0).all()
    # invalid candidates (id −1) never appear
    cids2 = cids.at[40:].set(-1)
    _, gi2 = kt_ops.knn_topk(qa, ca, qids, cids2, k=4, mode="interpret")
    assert (np.asarray(gi2) < 40).all()


def test_merge_running_topk():
    r = np.random.default_rng(1)
    d1 = jnp.asarray(np.sort(r.random((16, 4)), axis=1), jnp.float32)
    d2 = jnp.asarray(np.sort(r.random((16, 4)), axis=1), jnp.float32)
    i1 = jnp.asarray(r.integers(0, 100, (16, 4)), jnp.int32)
    i2 = jnp.asarray(r.integers(100, 200, (16, 4)), jnp.int32)
    md, mi = kt_ops.merge_running_topk(d1, i1, d2, i2, k=4)
    both = np.concatenate([np.asarray(d1), np.asarray(d2)], axis=1)
    want = np.sort(both, axis=1)[:, :4]
    np.testing.assert_allclose(np.asarray(md), want, rtol=1e-6)
    assert (np.diff(np.asarray(md), axis=1) >= 0).all()


@pytest.mark.parametrize("q,c,d", [(16, 64, 4), (64, 256, 24)])
@pytest.mark.parametrize("n_bins", [16, 64])
def test_bin_hist_matches_ref(q, c, d, n_bins):
    qa, ca = _data(q, c, d, jnp.float32)
    qids = jnp.arange(q, dtype=jnp.int32)
    cids = jnp.arange(c, dtype=jnp.int32)
    bw = jnp.float32(3.0 * np.sqrt(d) / n_bins)
    got = bh_ops.distance_bin_histogram(qa, ca, bw, n_bins,
                                        self_indices=qids, mode="interpret")
    want = bh_ref.distance_bin_histogram_ref(qa, ca, qids, cids, bw,
                                             n_bins=n_bins)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert int(got.sum()) > 0          # bins actually populated


def test_bin_hist_counts_every_pair_below_cutoff():
    qa, ca = _data(32, 128, 6, jnp.float32, seed=3)
    n_bins = 32
    bw = jnp.float32(10.0)             # huge bins: everything lands inside
    qids = jnp.full((32,), -1, jnp.int32)   # no self-exclusion
    got = bh_ops.distance_bin_histogram(qa, ca, bw, n_bins, mode="interpret")
    assert int(np.asarray(got).sum()) == 32 * 128


# ---------------------------------------------------------------------------
# kernel-mode parity: every dispatch path must agree (pallas compiled is
# TPU-only; interpret runs the same kernel body on CPU)
# ---------------------------------------------------------------------------

PARITY_MODES = ["ref", "interpret", "pallas"]


def _skip_unless_available(mode):
    if mode == "pallas" and jax.default_backend() != "tpu":
        pytest.skip("pallas compiled mode requires a TPU backend")


@pytest.mark.parametrize("mode", PARITY_MODES)
@pytest.mark.parametrize("q,c,d", [(32, 96, 8), (100, 300, 7)])
def test_pairwise_l2_mode_parity(mode, q, c, d):
    _skip_unless_available(mode)
    qa, ca = _data(q, c, d, jnp.float32, seed=5)
    got = pl_ops.pairwise_sq_l2(qa, ca, mode=mode)
    want = pl_ref.pairwise_sq_l2_ref(qa, ca)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", PARITY_MODES)
@pytest.mark.parametrize("q,c,d,k", [(32, 96, 8, 4), (64, 192, 24, 7)])
def test_knn_topk_mode_parity(mode, q, c, d, k):
    _skip_unless_available(mode)
    qa, ca = _data(q, c, d, jnp.float32, seed=6)
    qids = jnp.arange(q, dtype=jnp.int32)
    cids = jnp.arange(c, dtype=jnp.int32)
    gd, gi = kt_ops.knn_topk(qa, ca, qids, cids, k=k, mode=mode)
    wd, wi = kt_ref.knn_topk_ref(qa, ca, qids, cids, k=k)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(gi) == np.asarray(wi)).all()


def test_pairwise_l2_shortc_tile_skip_matches():
    """SHORTC's tile-level analogue must not change results."""
    qa, ca = _data(64, 128, 32, jnp.float32)
    base = pl_ops.pairwise_sq_l2(qa, ca, mode="interpret")
    eps2 = float(jnp.median(base))
    sc = pl_ops.pairwise_sq_l2(qa, ca, shortc_eps2=eps2, mode="interpret")
    # distances below the ε² cutoff must be exact; above may be clamped
    below = np.asarray(base) <= eps2
    np.testing.assert_allclose(np.asarray(sc)[below],
                               np.asarray(base)[below], rtol=1e-5)


def test_pairwise_l2_shortc_dynamic_eps_operand():
    """Traced ε² (runtime operand) must behave like the static constant —
    this is what lets the engines sweep ε without recompiling."""
    qa, ca = _data(48, 96, 32, jnp.float32, seed=7)
    base = pl_ops.pairwise_sq_l2(qa, ca, mode="interpret")
    eps2 = float(jnp.median(base))

    @jax.jit
    def dyn(q, c, e2):
        return pl_ops.pairwise_sq_l2(q, c, shortc_eps2=e2, mode="interpret")

    sc = dyn(qa, ca, jnp.float32(eps2))
    below = np.asarray(base) <= eps2
    np.testing.assert_allclose(np.asarray(sc)[below],
                               np.asarray(base)[below], rtol=1e-5)
    # exactness below the cutoff holds for a different ε on the SAME
    # executable (no retrace, the point of the dynamic operand)
    eps2_b = float(np.quantile(np.asarray(base), 0.9))
    sc_b = dyn(qa, ca, jnp.float32(eps2_b))
    below_b = np.asarray(base) <= eps2_b
    np.testing.assert_allclose(np.asarray(sc_b)[below_b],
                               np.asarray(base)[below_b], rtol=1e-5)


def test_knn_topk_oversized_k_falls_back_to_ref():
    """k beyond the kernel's unroll ceiling silently takes the ref merge
    path (same results), and the raw kernel refuses it loudly."""
    from repro.kernels.knn_topk import kernel as kt_kernel

    q, c, d = 16, 80, 6
    qa, ca = _data(q, c, d, jnp.float32, seed=8)
    qids = jnp.arange(q, dtype=jnp.int32)
    cids = jnp.arange(c, dtype=jnp.int32)
    k = kt_kernel.MAX_UNROLLED_K + 3
    gd, gi = kt_ops.knn_topk(qa, ca, qids, cids, k=k, mode="interpret")
    wd, wi = kt_ref.knn_topk_ref(qa, ca, qids, cids, k=k)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(gi) == np.asarray(wi)).all()
    with pytest.raises(ValueError, match="MAX_UNROLLED_K"):
        kt_kernel.knn_tile_topk(
            jnp.zeros((128, 8)), jnp.zeros((256, 8)),
            jnp.zeros((128,), jnp.int32), jnp.zeros((256,), jnp.int32),
            k=k, interpret=True)


@pytest.mark.parametrize("fn", ["pairwise", "topk"])
def test_l2_kernels_exact_under_cancellation(fn):
    """Points far from the origin relative to their spacing: the MXU
    expansion ‖q‖²+‖c‖²−2q·c cancels its f32 digits there, the direct
    differences the kernels sum do not."""
    r = np.random.default_rng(8)
    base = r.normal(size=(1, 18)) * 4.0
    qa = (base + 1e-2 * r.normal(size=(40, 18))).astype(np.float32)
    ca = (base + 1e-2 * r.normal(size=(200, 18))).astype(np.float32)
    diff = qa.astype(np.float64)[:, None, :] - ca.astype(np.float64)[None]
    want = np.sum(diff * diff, axis=-1)
    if fn == "pairwise":
        got = np.asarray(pl_ops.pairwise_sq_l2(qa, ca, mode="interpret"))
    else:
        got, _ = kt_ops.knn_topk(
            jnp.asarray(qa), jnp.asarray(ca),
            jnp.arange(40, dtype=jnp.int32),
            jnp.arange(40, 240, dtype=jnp.int32), k=8, mode="interpret")
        got, want = np.asarray(got), np.sort(want, axis=1)[:, :8]
    np.testing.assert_allclose(got, want, rtol=1e-5)
