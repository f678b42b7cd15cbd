"""kNN-LM retrieval head — the paper's join as a first-class LM feature.

At serve time the decoder's final hidden state queries a datastore of
(hidden, next-token) pairs; the output distribution is

    p(w) = λ · p_kNN(w)  +  (1 − λ) · p_LM(w),
    p_kNN(w) ∝ Σ_{i : v_i = w} exp(−d_i² / T)          (Khandelwal et al.)

The lookup engine *is* the paper's machinery (DESIGN.md §3.3):

  * replicated datastore  -> the streamed fused-top-K dense engine
    (``core.brute.brute_knn``: grid-free, MXU tile join — the hot serving
    path for datastores that fit per-device HBM);
  * sharded datastore     -> the ring-systolic join over the "model" mesh
    axis (``sharded_lookup``): each device holds a datastore shard, the
    query batch visits all shards via ppermute, exact global top-K.
  * analytics / offline   -> ``HybridKNNJoin`` builds the datastore's own
    self-join (e.g. datastore dedup), reusing β/γ/ρ untouched.

Keys are stored in the *reordered, variance-ranked* space (§IV-D) and
can be PCA-free dimension-truncated (m < n, §IV-C) — both paper
optimizations apply verbatim to retrieval.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import brute as brute_lib
from repro.core import grid as grid_lib
from repro.kernels.knn_topk import ops as topk_ops
from repro.models import transformer
from repro import utils


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Datastore:
    keys: jnp.ndarray      # (N, d_key) float32, reordered space
    values: jnp.ndarray    # (N,) int32 next-token ids
    order: jnp.ndarray     # (d,) variance reorder permutation (§IV-D)

    def tree_flatten(self):
        return (self.keys, self.values, self.order), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def size(self) -> int:
        return self.keys.shape[0]


def collect_pairs(params, cfg: ModelConfig,
                  token_batches: Sequence) -> Tuple[np.ndarray, np.ndarray]:
    """Run the LM over token batches; return the raw (hidden_t ->
    token_{t+1}) pairs as ``(keys (N, d) f32, values (N,) i32)`` — the
    shared front half of every datastore flavor."""
    keys, vals = [], []
    for tokens in token_batches:
        hidden, _, _ = transformer.forward_seq(params, cfg, tokens)
        keys.append(np.asarray(hidden[:, :-1].astype(jnp.float32))
                    .reshape(-1, hidden.shape[-1]))
        vals.append(np.asarray(tokens[:, 1:]).reshape(-1))
    return (np.concatenate(keys),
            np.concatenate(vals).astype(np.int32))


def build_datastore(params, cfg: ModelConfig, token_batches: Sequence,
                    *, m_dims: Optional[int] = None) -> Datastore:
    """Collect (hidden_t -> token_{t+1}) pairs into the replicated
    in-jit datastore.  ``m_dims`` truncates keys to the top-variance
    dims (§IV-C: index fewer dims, exactness preserved by re-ranking at
    full dim — for retrieval the truncation is the approximation knob)."""
    raw_keys, raw_vals = collect_pairs(params, cfg, token_batches)
    reordered, order = grid_lib.reorder_by_variance(jnp.asarray(raw_keys))
    if m_dims is not None:
        reordered = reordered[:, :m_dims]
    return Datastore(keys=reordered, values=jnp.asarray(raw_vals),
                     order=order)


def _project(ds: Datastore, queries: jnp.ndarray) -> jnp.ndarray:
    """Apply the datastore's REORDER permutation (+ truncation) to queries."""
    q = queries.astype(jnp.float32)[:, ds.order]
    return q[:, : ds.keys.shape[1]]


@functools.partial(jax.jit, static_argnames=("k", "corpus_chunk"))
def lookup(ds: Datastore, queries: jnp.ndarray, *, k: int,
           corpus_chunk: int = 4096) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Replicated-datastore lookup: (d² (B,k), values (B,k))."""
    q = _project(ds, queries)
    qids = ds.size + jnp.arange(q.shape[0], dtype=jnp.int32)  # no self-excl.
    d2, ids = brute_lib.brute_knn(ds.keys, q, qids, k=k,
                                  corpus_chunk=corpus_chunk)
    vals = ds.values[jnp.clip(ids, 0, ds.size - 1)]
    vals = jnp.where(ids >= 0, vals, -1)
    return d2, vals


def sharded_lookup(mesh: Mesh, axis: str, *, k: int):
    """Ring lookup for datastores sharded over ``axis`` (the corpus shard
    rotates; queries stay resident — exact global top-K in
    ``mesh.shape[axis]`` neighbor-to-neighbor hops)."""
    n_shards = mesh.shape[axis]
    ring = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def local(q, keys, vals):
        run_d = jax.lax.pcast(
            jnp.full((q.shape[0], k), jnp.inf, jnp.float32), axis, to="varying")
        run_v = jax.lax.pcast(
            jnp.full((q.shape[0], k), -1, jnp.int32), axis, to="varying")

        def step(_, carry):
            rd, rv, ks, vs = carry
            qids = vs.shape[0] * n_shards + jnp.arange(
                q.shape[0], dtype=jnp.int32)
            nd, ni = topk_ops.knn_topk(
                q, ks, qids, jnp.arange(ks.shape[0], dtype=jnp.int32), k=k)
            nv = jnp.where(ni >= 0, vs[jnp.clip(ni, 0, vs.shape[0] - 1)], -1)
            rd, rv = topk_ops.merge_running_topk(rd, rv, nd, nv, k=k)
            ks = jax.lax.ppermute(ks, axis, ring)
            vs = jax.lax.ppermute(vs, axis, ring)
            return rd, rv, ks, vs

        rd, rv, _, _ = jax.lax.fori_loop(
            0, n_shards, step, (run_d, run_v, keys, vals))
        return rd, rv

    shard_fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(), P()),
        check_vma=False)   # after a full ring rotation every device holds
    return shard_fn        # the identical exact top-K (invariance by
                           # construction, not statically provable)


def knn_probs(d2: jnp.ndarray, vals: jnp.ndarray, vocab: int,
              temperature: float) -> jnp.ndarray:
    """Scatter exp(−d²/T) onto the vocabulary.  (B,k) -> (B,V)."""
    w = jax.nn.softmax(jnp.where(vals >= 0, -d2 / temperature, -jnp.inf),
                       axis=-1)
    w = jnp.where(vals >= 0, w, 0.0)
    b, k = vals.shape
    out = jnp.zeros((b, vocab), jnp.float32)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, k))
    return out.at[rows, jnp.clip(vals, 0, vocab - 1)].add(w)


class IndexRetriever:
    """kNN-LM lookup served by the index stack (DESIGN.md §9.5): the
    datastore keys live in a ``KNNIndex`` / ``ShardedKNNIndex`` built
    with ``metric="ip"`` (maximum-inner-product retrieval — the scoring
    the LM's unembed actually uses), and hidden-state queries enter
    through the ``KNNServer`` admission/micro-batching front-end.

    This is the *served* datastore: mutable (``insert`` new pairs as
    text streams in), persistent (``index.save``/``load``), shardable
    across devices — everything the in-jit ``Datastore`` pytree is not.
    The trade is that the lookup runs host-side between decode steps
    instead of inside the jitted step, so it pairs with the
    ``generate``-level interpolation path rather than
    ``decode_step_retrieval``.
    """

    def __init__(self, index, values: np.ndarray, *, server=None):
        self.index = index
        self.values = np.asarray(values, np.int32)
        self.server = server

    @classmethod
    def build(cls, params, cfg: ModelConfig, token_batches: Sequence, *,
              mesh=None, hybrid_config=None, server_config=None):
        """Collect (hidden, next-token) pairs and index the keys with
        ``metric="ip"``.  ``mesh`` shards the datastore (one corpus
        partition per device, collective top-K merge); ``server_config``
        wraps the index in a ``KNNServer`` front-end."""
        from repro.core.hybrid import HybridConfig
        from repro.runtime.knn_index import KNNIndex
        from repro.runtime.server import KNNServer

        keys, vals = collect_pairs(params, cfg, token_batches)
        rc = cfg.retrieval
        hcfg = hybrid_config or HybridConfig(k=rc.k, metric="ip")
        if hcfg.metric != "ip":
            raise ValueError(
                f"IndexRetriever scores candidates by inner product (the "
                f"unembed's own geometry); got metric={hcfg.metric!r} — "
                f"pass a HybridConfig with metric='ip'")
        index = KNNIndex.build(keys, hcfg, mesh=mesh)
        server = None
        if server_config is not None:
            server = KNNServer(index, server_config)
        return cls(index, vals, server=server)

    @property
    def size(self) -> int:
        return self.index.n_points

    def insert(self, params, cfg: ModelConfig, token_batches: Sequence):
        """Stream new text into the served datastore (delta-buffer
        insert — no rebuild until compaction)."""
        keys, vals = collect_pairs(params, cfg, token_batches)
        self.index.insert(keys)
        self.values = np.concatenate([self.values, vals])

    def lookup(self, queries: np.ndarray, *,
               k: int) -> Tuple[np.ndarray, np.ndarray]:
        """(B, d) hidden states -> (scores (B, k), values (B, k)).

        Scores are the index's finalized ip distances (−q·c), so
        ``knn_probs``'s exp(−d/T) weighting becomes exp(q·c/T) — the
        inner-product kNN-LM head.  Through the server each row is one
        admitted request; the micro-batcher re-coalesces them, so the
        answers are bit-identical to a direct whole-batch query."""
        q = np.asarray(queries, np.float32)
        if self.server is not None:
            tickets = [self.server.submit(row, k=k) for row in q]
            self.server.drain()
            bad = [t for t in tickets if not hasattr(t.outcome, "ids")]
            if bad:
                raise RuntimeError(
                    f"{len(bad)} of {len(tickets)} retrieval requests "
                    f"were shed ({bad[0].outcome!r}) — a decode step "
                    f"cannot proceed on partial retrieval; raise the "
                    f"server deadline or queue bound")
            d = np.stack([t.outcome.dists for t in tickets])
            ids = np.stack([t.outcome.ids for t in tickets])
        else:
            res = self.index.query(q, k=k)
            d, ids = np.asarray(res.dists), np.asarray(res.ids)
        vals = np.where(ids >= 0,
                        self.values[np.clip(ids, 0, len(self.values) - 1)],
                        -1)
        return d, vals


def interpolate_retrieval(cfg: ModelConfig, logits, d: np.ndarray,
                          vals: np.ndarray):
    """λ·p_kNN + (1−λ)·p_LM from already-retrieved (scores, values) —
    the host-side back half of ``decode_step_retrieval`` for
    index-backed lookups that run between jitted decode steps."""
    rc = cfg.retrieval
    p_lm = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    p_knn = knn_probs(jnp.asarray(d), jnp.asarray(vals), cfg.vocab_size,
                      rc.temperature)
    p = rc.lam * p_knn + (1.0 - rc.lam) * p_lm
    return jnp.log(jnp.maximum(p, 1e-20))


def decode_step_retrieval(params, cfg: ModelConfig, token, cache, pos,
                          ds: Datastore, shd=None):
    """transformer.decode_step + kNN interpolation (serving hot path).

    One pass through the stack: the final-norm hidden state is both the
    unembed input (p_LM) and the retrieval query (p_kNN)."""
    from repro.models import layers as L
    rc = cfg.retrieval
    hidden, new_cache = transformer.decode_step_hidden(
        params, cfg, token, cache, pos, shd)
    logits = L.unembed(params["embed"], cfg, hidden[:, None])[:, 0]
    p_lm = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    d2, vals = lookup(ds, hidden, k=rc.k)
    p_knn = knn_probs(d2, vals, cfg.vocab_size, rc.temperature)
    p = rc.lam * p_knn + (1.0 - rc.lam) * p_lm
    return jnp.log(jnp.maximum(p, 1e-20)), new_cache
