"""The plain reference that decides ``correct``, and its lower-precision
control.  Imports nothing of the program under test.

The configurations state exact k-nearest-neighbour answers under float32
Euclidean distance: each answered row holds k distinct corpus ids (never
the row's own id when it excludes itself), in ascending distance, with the
distances that those ids really have.  The check holds every row a timed
call returned to that, with three numbers:

``bad_rows``       rows whose ids are out of range, repeated, the row's own
                   id under ``exclude_self``, or whose distances do not
                   ascend (limit 0);
``dist_rel_err``   the widest gap between a reported distance and the
                   float64 distance of the id reported beside it, over the
                   row's float64 k-th distance;
``missed``         over a seeded sample of rows: corpus points closer than
                   the row's k-th answer by more than ``MARGIN`` (relative,
                   in squared distance) that the row does not hold (limit 0).
                   Counted on the device in float32 from direct differences,
                   whose rounding (~1e-6 relative) stays inside the margin.

``bad_rows`` and ``dist_rel_err`` cover every answered row; ``missed``
scans the whole corpus for up to ``sample_rows`` rows, drawn from the seed
with every answering lane (dense, sparse, brute) represented.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import clouds

MARGIN = 1e-5
CHUNK = 1 << 16


def row_checks(corpus: np.ndarray, queries: np.ndarray, ids: np.ndarray,
               dists: np.ndarray, excl: np.ndarray):
    """Host float64 checks of answered rows.  Returns (bad (rows,) bool,
    rel_err (rows,) float64, thr2 (rows,) the float64 squared k-th
    distance less the margin)."""
    n = len(corpus)
    k = ids.shape[1]
    in_range = np.all((ids >= 0) & (ids < n), axis=1)
    srt = np.sort(ids, axis=1)
    distinct = np.all(srt[:, 1:] != srt[:, :-1], axis=1) if k > 1 else True
    not_self = np.all(ids != excl[:, None], axis=1)
    ascending = np.all(np.diff(dists, axis=1) >= 0, axis=1)
    finite = np.all(np.isfinite(dists), axis=1)
    bad = ~(in_range & distinct & not_self & ascending & finite)
    safe = np.clip(ids, 0, n - 1)
    true2 = np.zeros(ids.shape)
    q64 = queries.astype(np.float64)
    for j in range(k):
        diff = corpus[safe[:, j]].astype(np.float64) - q64
        true2[:, j] = np.einsum("ij,ij->i", diff, diff)
    true = np.sqrt(true2)
    kth = np.maximum(true.max(axis=1), np.finfo(np.float64).tiny)
    rel = np.abs(dists.astype(np.float64) - true) / kth[:, None]
    rel = np.where(np.isfinite(rel), rel, np.inf).max(axis=1)
    thr2 = true2.max(axis=1) * (1.0 - MARGIN)
    return bad, rel, thr2


@functools.partial(jax.jit, static_argnames=("chunk",))
def _missed(corpus_t, queries, ids, excl, thr2, *, chunk: int):
    """Per row, the corpus points with squared float32 distance below
    ``thr2`` that are neither answered (``ids``) nor the row itself."""
    d, n = corpus_t.shape
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    c = jnp.pad(corpus_t, ((0, 0), (0, pad)))

    def body(i, count):
        blk = jax.lax.dynamic_slice_in_dim(c, i * chunk, chunk, axis=1)
        cid = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        acc = jnp.zeros((queries.shape[0], chunk), jnp.float32)
        for j in range(d):
            diff = blk[j][None, :] - queries[:, j:j + 1]
            acc = acc + diff * diff
        closer = (acc < thr2[:, None]) & (cid[None, :] < n)
        closer &= cid[None, :] != excl[:, None]
        for j in range(ids.shape[1]):
            closer &= cid[None, :] != ids[:, j:j + 1]
        return count + jnp.sum(closer, axis=1, dtype=jnp.int32)

    return jax.lax.fori_loop(0, n_chunks, body,
                             jnp.zeros((queries.shape[0],), jnp.int32))


def count_missed(corpus: np.ndarray, queries: np.ndarray, ids: np.ndarray,
                 excl: np.ndarray, thr2: np.ndarray, block: int = 512):
    """``missed`` per row, on the device, ``block`` rows at a time."""
    corpus_t = jnp.asarray(np.ascontiguousarray(corpus.T))
    out = []
    for lo in range(0, len(queries), block):
        sl = slice(lo, lo + block)
        rows = len(queries[sl])
        pad = block - rows
        out.append(np.asarray(_missed(
            corpus_t,
            jnp.asarray(np.pad(queries[sl], ((0, pad), (0, 0)))),
            jnp.asarray(np.pad(ids[sl], ((0, pad), (0, 0)),
                               constant_values=-1)),
            jnp.asarray(np.pad(excl[sl], (0, pad), constant_values=-1)),
            jnp.asarray(np.pad(thr2[sl], (0, pad)).astype(np.float32)),
            chunk=min(CHUNK, -(-len(corpus) // 128) * 128)))[:rows])
    return np.concatenate(out) if out else np.zeros((0,), np.int32)


def sample_rows(sources: np.ndarray, n_sample: int, seed: int) -> np.ndarray:
    """Indices of up to ``n_sample`` answered rows drawn from ``seed``,
    split evenly over the lanes that answered (``sources``), so that a lane
    with few rows is checked too."""
    rng = clouds.seed_rng(seed, 3)
    lanes = sorted((np.nonzero(sources == s)[0] for s in np.unique(sources)),
                   key=len)
    picked = []
    for i, rows in enumerate(lanes):
        take = min(len(rows), n_sample // (len(lanes) - i))
        picked.append(rng.choice(rows, take, replace=False))
        n_sample -= take
    return np.sort(np.concatenate(picked)) if picked else np.zeros(0, int)


def check(corpus: np.ndarray, queries: np.ndarray, ids: np.ndarray,
          dists: np.ndarray, sources: np.ndarray, excl: np.ndarray,
          seed: int, sample_rows_n: int) -> dict:
    """The comparison: every answered row (``queries`` (R, D), ``ids`` and
    ``dists`` (R, k), ``sources`` (R,) the lane of each row, ``excl`` (R,)
    the corpus id each row excludes or −1).  Returns the compared numbers,
    the rows checked and the answering lanes."""
    bad, rel, thr2 = row_checks(corpus, queries, ids, dists, excl)
    pick = sample_rows(sources, sample_rows_n, seed)
    pick = pick[~bad[pick]]
    missed = count_missed(corpus, queries[pick], ids[pick], excl[pick],
                          thr2[pick])
    return {
        "bad_rows": int(bad.sum()),
        "dist_rel_err": float(rel.max()) if len(rel) else float("inf"),
        "missed": int(missed.sum()),
        "rows": int(len(ids)),
        "rows_scanned": int(len(pick)),
        "lanes": {int(s): int(np.sum(sources == s))
                  for s in np.unique(sources)},
    }


# --- the control: the reference in the program's place, in bfloat16 -------

@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def _bf16_knn(corpus_t, queries, excl, *, k: int, chunk: int):
    d, n = corpus_t.shape
    n_chunks = -(-n // chunk)
    c = jnp.pad(corpus_t, ((0, 0), (0, n_chunks * chunk - n))).astype(
        jnp.bfloat16)
    q = queries.astype(jnp.bfloat16)
    nq = queries.shape[0]

    def body(i, carry):
        best_d, best_i = carry
        blk = jax.lax.dynamic_slice_in_dim(c, i * chunk, chunk, axis=1)
        cid = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        acc = jnp.zeros((nq, chunk), jnp.float32)
        for j in range(d):
            diff = blk[j][None, :] - q[:, j:j + 1]          # bfloat16
            acc = acc + (diff * diff).astype(jnp.float32)
        bad = (cid[None, :] >= n) | (cid[None, :] == excl[:, None])
        acc = jnp.where(bad, jnp.inf, acc)
        nd, pos = jax.lax.top_k(-acc, k)
        all_d = jnp.concatenate([best_d, -nd], axis=1)
        all_i = jnp.concatenate([best_i, cid[pos]], axis=1)
        top, sel = jax.lax.top_k(-all_d, k)
        return -top, jnp.take_along_axis(all_i, sel, axis=1)

    init = (jnp.full((nq, k), jnp.inf, jnp.float32),
            jnp.full((nq, k), -1, jnp.int32))
    best_d, best_i = jax.lax.fori_loop(0, n_chunks, body, init)
    return jnp.sqrt(best_d), best_i


class Bf16Control:
    """Stands in for ``index.query``: exact brute-force kNN computed with
    bfloat16 coordinates and differences (float32 sums), the precision
    below the float32 that the configurations state."""

    def __init__(self, corpus: np.ndarray, k: int):
        self.corpus_t = jnp.asarray(np.ascontiguousarray(corpus.T))
        self.k = k
        self.chunk = min(CHUNK // 4, -(-len(corpus) // 128) * 128)

    def query(self, queries: np.ndarray, exclude_self: bool = False):
        nq = len(queries)
        excl = (np.arange(nq, dtype=np.int32) if exclude_self
                else np.full((nq,), -1, np.int32))
        d, i = _bf16_knn(self.corpus_t, jnp.asarray(queries),
                         jnp.asarray(excl), k=self.k, chunk=self.chunk)
        return np.asarray(d), np.asarray(i)
