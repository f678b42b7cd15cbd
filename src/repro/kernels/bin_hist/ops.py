"""Public wrapper for the ε-selection distance histogram."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_pallas
from repro.utils import round_up
from repro.kernels.bin_hist import kernel as _kernel
from repro.kernels.bin_hist import ref as _ref


@functools.partial(
    jax.jit, static_argnames=("n_bins", "block_q", "block_c", "mode")
)
def distance_bin_histogram(
    queries: jnp.ndarray,    # (S, D) sampled query points
    points: jnp.ndarray,     # (N, D) full database
    bin_width: jnp.ndarray,  # () f32
    n_bins: int,
    *,
    self_indices: jnp.ndarray | None = None,  # (S,) ids of queries within points
    block_q: int = 128,
    block_c: int = 512,
    mode: str = "auto",
) -> jnp.ndarray:
    """(n_bins,) counts of pairwise distances < n_bins·bin_width."""
    s, d = queries.shape
    n, _ = points.shape
    qid = (
        self_indices.astype(jnp.int32)
        if self_indices is not None
        # No self-exclusion wanted: ids beyond the point-id range (valid,
        # never equal to any point id).
        else n + jnp.arange(s, dtype=jnp.int32)
    )
    pid = jnp.arange(n, dtype=jnp.int32)
    bw = jnp.asarray(bin_width, jnp.float32)

    if not use_pallas(mode):
        return _ref.distance_bin_histogram_ref(
            queries, points, qid, pid, bw, n_bins=n_bins
        )

    sp = round_up(max(s, 1), block_q)
    np_ = round_up(max(n, 1), block_c)
    q = jnp.zeros((sp, d), queries.dtype).at[:s].set(queries)
    p = jnp.zeros((np_, d), points.dtype).at[:n].set(points)
    qidp = jnp.full((sp,), -1, jnp.int32).at[:s].set(qid)
    pidp = jnp.full((np_,), -1, jnp.int32).at[:n].set(pid)
    return _kernel.distance_bin_histogram(
        q, p, qidp, pidp, bw,
        n_bins=n_bins, block_q=block_q, block_c=block_c,
        interpret=(mode == "interpret"),
    )
