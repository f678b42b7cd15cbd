"""Multi-round work-queue scheduler (paper §V-A, §V-F, Table III).

The paper's host loop keeps the GPU fed with *batches* of dense-region
queries pulled from a shared work queue while the CPU ranks drain the
sparse region concurrently; the number of batches (the Table III
granularity knob) bounds the terminal load imbalance to one batch, and
the per-query engine costs T₁/T₂ measured on the first round feed
ρ^Model (Eq. 6) so the dense/sparse split is corrected *online* rather
than fixed by the static ρ parameter.  Gowanlock & Karsin's self-join
work (arXiv:1809.09930) uses the same batched-dequeue idiom.

This module is engine-agnostic: the scheduler receives three callables
(dense, sparse, brute) and never touches jax beyond readiness polling,
so tests can drive it with numpy stubs and the session can inject its
cached compiled executables.

Scheduling contract:

  * ``WorkQueue`` holds the dense assignment sorted by home-cell
    population, densest first.  Batches are dequeued from the head;
    online demotion pops from the tail — the paper's §V-F rule that the
    sparse engine takes "cells with the least number of points".
  * The sparse round is dispatched asynchronously (JAX async dispatch:
    the engine call returns an :class:`AsyncEngineCall` immediately) and
    harvested between dense batches.
  * Work only ever moves dense → sparse (demotion, §V-E failure
    reassignment).  The sparse assignment is therefore monotonically
    non-decreasing, so the splitter's ρ floor of ``ceil(ρ·|D|)`` sparse
    queries can never be starved by rebalancing.

Measurement caveat: T₁ is the wall time from sparse dispatch to
harvest.  On a single shared device the dense batches executed in
between inflate it (dispatch queues are FIFO), making ρ^online an upper
bound on the true sparse share — demotion errs toward the engine whose
results are already certified exactly, so correctness is unaffected.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import splitter as split_lib
from repro.runtime import trace

_compile_seconds = 0.0      # host seconds spent compiling engines


def charge_compile(seconds: float) -> None:
    """Record ``seconds`` of host-side engine compilation (the AOT
    engine cache calls this on every miss)."""
    global _compile_seconds
    _compile_seconds += seconds


def engine_clock() -> float:
    """Wall clock that stands still while the host compiles an engine.
    Engine timings read it, so a sparse round in flight across a dense
    engine's compile times its execution, not the compiler (T₁ and T₂
    are execution costs)."""
    return time.perf_counter() - _compile_seconds


class AsyncEngineCall:
    """Handle over an in-flight (async-dispatched) engine invocation.

    ``raw`` is any pytree of device arrays (or numpy arrays, for stub
    engines — those are trivially ready).  ``finalize`` converts the
    blocked raw tree into the scheduler-facing result tuple.
    """

    def __init__(self, raw, finalize: Optional[Callable] = None):
        self._raw = raw
        self._finalize = finalize or (lambda x: x)
        # Construction happens after the engine's own compile, and
        # engine_clock skips later ones, so dispatch→get measures
        # execution (plus any host wait), not tracing/lowering.
        self.t_dispatch = engine_clock()
        self.elapsed: Optional[float] = None

    def ready(self) -> bool:
        """Non-blocking readiness poll (conservative: unknown ⇒ not ready)."""
        for leaf in jax.tree_util.tree_leaves(self._raw):
            is_ready = getattr(leaf, "is_ready", None)
            if is_ready is not None and not is_ready():
                return False
        return True

    def get(self):
        with trace.span("knn.sparse.wait"):
            jax.block_until_ready(self._raw)
        if self.elapsed is None:
            self.elapsed = engine_clock() - self.t_dispatch
        with trace.span("knn.sparse.copy"):
            return self._finalize(self._raw)


@dataclasses.dataclass
class QueueReport:
    """Per-run accounting the session folds into ``JoinStats``."""

    batch_sizes: List[int] = dataclasses.field(default_factory=list)
    t_batches: List[float] = dataclasses.field(default_factory=list)
    n_dense_batches: int = 0
    n_sparse_rounds: int = 0
    n_rebalanced: int = 0            # queries demoted online (beyond ρ floor)
    n_failed: int = 0                # dense failures reassigned (§V-E)
    n_uncertified: int = 0           # sparse results needing the brute lane
    n_sparse_engine_total: int = 0   # every query the sparse engine saw
    n_sparse_gated: int = 0          # sparse-bound rows the capacity gate
                                     # sent to the brute lane unseen
    t_dense: float = 0.0
    t_sparse: float = 0.0
    t_brute: float = 0.0
    t_wall: float = 0.0              # true scheduler wall time (engines
                                     # overlap, so this < sum of the above)
    t1_per_query: float = 0.0        # paper T₁ (sparse engine)
    t2_per_query: float = 0.0        # paper T₂ (dense engine)
    rho_online: float = 0.0          # last Eq. 6 estimate used for demotion
    # Engine counters, each reduced to a scalar on the device and added
    # here by the engine closures' harvests (0 for stub engines).
    n_dense_overflow: int = 0        # real dense rows failed by overflow
    dense_pairs: int = 0             # query–candidate pairs the dense
                                     # kernel scored
    dense_cand_rows: int = 0         # candidate rows it read
    n_sparse_overflow: int = 0       # uncertified sparse rows whose
                                     # budget overflowed


class WorkQueue:
    """Dense-engine work queue with head dequeue and tail demotion.

    The id array is sorted by home-cell population descending, so the
    head holds the densest queries (most MXU-friendly work first) and
    the tail holds the queries closest to the density threshold — the
    ones the paper demotes when ρ must rise.
    """

    def __init__(
        self,
        dense_ids: Sequence[int],
        home_counts: Sequence[int],
        n_batches: int = 1,
    ):
        ids = np.asarray(dense_ids, np.int32)
        if len(ids):
            counts = np.asarray(home_counts)[ids]
            order = np.argsort(-counts, kind="stable")
            ids = ids[order]
        self._ids = ids
        self._counts = (
            np.asarray(home_counts)[ids] if len(ids) else np.zeros((0,), np.int64)
        )
        self._head = 0
        self._tail = len(ids)
        self.n_batches = max(int(n_batches), 1)
        self.batch_size = (
            -(-len(ids) // self.n_batches) if len(ids) else 0
        )
        self.n_demoted = 0

    @property
    def remaining(self) -> int:
        return self._tail - self._head

    def next_batch(self) -> np.ndarray:
        """Dequeue up to ``batch_size`` ids from the dense (head) end."""
        take = min(self.batch_size, self.remaining)
        out = self._ids[self._head : self._head + take]
        self._head += take
        return out

    def demote(self, n: int) -> np.ndarray:
        """Pop ≤ n ids off the tail (least-populated home cells first in
        the returned array).  Never touches work already dequeued."""
        take = min(max(int(n), 0), self.remaining)
        out = self._ids[self._tail - take : self._tail][::-1].copy()
        self._tail -= take
        self.n_demoted += take
        return out

    def peek_tail_counts(self, n: int) -> np.ndarray:
        """Home-cell populations of the next-to-demote queries (tests)."""
        take = min(max(int(n), 0), self.remaining)
        return self._counts[self._tail - take : self._tail][::-1].copy()


def _concat(parts: List[np.ndarray]) -> np.ndarray:
    parts = [p for p in parts if len(p)]
    if not parts:
        return np.zeros((0,), np.int32)
    return np.concatenate(parts).astype(np.int32)


def run_work_queue(
    *,
    npts: int,
    k: int,
    dense_ids: np.ndarray,
    sparse_ids: np.ndarray,
    home_counts: np.ndarray,
    dense_fn: Callable[[np.ndarray], Tuple[np.ndarray, np.ndarray, np.ndarray]],
    sparse_fn: Callable[[np.ndarray], AsyncEngineCall],
    brute_fn: Callable[[np.ndarray], Tuple[np.ndarray, np.ndarray]],
    n_batches: int = 1,
    online_rebalance: bool = True,
    sync_t1_after: int = 1,
    min_sparse: int = 0,
    demote_quantum: int = 1,
    report: Optional[QueueReport] = None,
    sparse_ok: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, QueueReport]:
    """Drive one join through the multi-round queue.

    The scheduler is id-space agnostic: ids are *query* ids — indices
    into whatever query set the engines were closed over (the indexed
    cloud itself for a self-join, an arbitrary R≠S query batch for
    ``KNNIndex.query``) — and ``npts`` is |Q|, the size of that query
    set (the result arrays' first axis).

    Engine contract (all ids are query ids, no padding):
      ``dense_fn(ids) -> (dists (n,K), nids (n,K), failed (n,) bool,
          elapsed_s)`` — blocking; ``elapsed_s`` is the engine-measured
          execution time excluding one-time compilation, so T₂ isn't
          polluted by a cold cache; failures are reassigned to the
          sparse engine.
      ``sparse_fn(ids) -> AsyncEngineCall`` yielding
          ``(dists, nids, certified (n,) bool)`` — dispatched async;
          uncertified rows fall through to the brute lane.
      ``brute_fn(ids) -> (dists, nids)`` — blocking, always exact.

    ``sync_t1_after`` forces a blocking T₁ harvest after that many dense
    batches if the async poll has not succeeded yet (0 disables), so the
    rebalance point is deterministic across backends.  ``demote_quantum``
    is the minimum online demotion (one engine query block): deficits
    smaller than it are not worth a dedicated sparse round.  ``report``
    is the record to fill, one the engines' harvests also add their
    counters to; a fresh one by default.

    ``sparse_ok`` ((|Q|,) bool, optional) is the capacity gate: a row it
    refuses is one the sparse engine can never certify, so every round
    (initial, demoted backlog, terminal failures) sends it straight to
    the brute lane instead, counted in ``n_sparse_gated`` and not in
    ``n_sparse_engine_total`` or ``n_uncertified``.  A round the gate
    empties is not dispatched.  The ρ floor and the online demotion
    count gated rows with the sparse ones: both are outside the dense
    lane.  None dispatches every row.

    Returns ``(final_d, final_i, source, report)`` with ``final_d`` in
    squared-L2 (callers sqrt), ``source`` ∈ {0: dense, 1: sparse,
    2: brute}.
    """
    dense_ids = np.asarray(dense_ids, np.int32)
    sparse_ids = np.asarray(sparse_ids, np.int32)
    if len(sparse_ids) < min_sparse:
        raise ValueError(
            f"initial sparse assignment {len(sparse_ids)} violates the "
            f"ρ floor {min_sparse} — splitter must enforce it first"
        )

    t_start = time.perf_counter()
    final_d = np.full((npts, k), np.inf, np.float32)
    final_i = np.full((npts, k), -1, np.int32)
    source = np.full((npts,), 1, np.int8)
    report = QueueReport() if report is None else report

    queue = WorkQueue(dense_ids, home_counts, n_batches)
    backlog: List[np.ndarray] = []     # demoted, awaiting a sparse round
    failed: List[np.ndarray] = []      # dense failures (§V-E)
    uncertified: List[np.ndarray] = []
    gated: List[np.ndarray] = []       # refused by sparse_ok
    inflight: Optional[Tuple[np.ndarray, AsyncEngineCall, float]] = None
    t1: Optional[float] = None
    t2: Optional[float] = None
    dense_ok_total = 0

    def dispatch_sparse(ids: np.ndarray, pure: bool = True) -> None:
        """``pure=False`` marks the terminal round that carries §V-E
        dense failures — it still runs on the sparse engine but must not
        feed the T₁ load model."""
        nonlocal inflight
        if sparse_ok is not None:
            ok = sparse_ok[ids]
            gated.append(ids[~ok])
            report.n_sparse_gated += len(gated[-1])
            ids = ids[ok]
            if not len(ids):
                return
        t0 = engine_clock()
        with trace.span("knn.sparse.dispatch"):
            inflight = (ids, sparse_fn(ids), t0, pure)
        report.n_sparse_rounds += 1
        report.n_sparse_engine_total += len(ids)

    def harvest_sparse() -> None:
        nonlocal inflight, t1
        ids, handle, t0, pure = inflight
        d, i, cert = handle.get()
        dt = handle.elapsed if handle.elapsed is not None else (
            engine_clock() - t0
        )
        inflight = None
        report.t_sparse += dt
        cert = np.asarray(cert, bool)
        cid = ids[cert]
        final_d[cid] = np.asarray(d)[cert]
        final_i[cid] = np.asarray(i)[cert]
        source[cid] = 1
        uncertified.append(ids[~cert])
        if len(ids) and (pure or t1 is None):
            t1 = dt / len(ids)
            report.t1_per_query = t1

    if len(sparse_ids):
        dispatch_sparse(sparse_ids)

    while queue.remaining:
        batch = queue.next_batch()
        d, i, fail, dt = dense_fn(batch)
        report.n_dense_batches += 1
        report.batch_sizes.append(int(len(batch)))
        report.t_batches.append(dt)
        report.t_dense += dt
        fail = np.asarray(fail, bool)
        ok = batch[~fail]
        final_d[ok] = np.asarray(d)[~fail]
        final_i[ok] = np.asarray(i)[~fail]
        source[ok] = 0
        failed.append(batch[fail])
        dense_ok_total += len(ok)
        if len(batch):
            t2 = dt / len(batch)

        if inflight is not None and (
            inflight[1].ready()
            or (
                sync_t1_after
                and t1 is None
                and report.n_dense_batches >= sync_t1_after
            )
        ):
            harvest_sparse()

        if (
            online_rebalance
            and t1 is not None
            and t2 is not None
            and queue.remaining
        ):
            with trace.span("knn.queue.rebalance"):
                rho_online = split_lib.rho_model(t1, t2)
                report.rho_online = rho_online
                assigned = (
                    report.n_sparse_engine_total + report.n_sparse_gated
                    + sum(len(b) for b in backlog)
                )
                deficit = int(math.ceil(rho_online * npts)) - assigned
                # Slivers below one engine block aren't worth a round; the
                # engine-side _pad_ids pow2 padding bounds compiled shapes.
                if (deficit < queue.remaining
                        and deficit < max(demote_quantum, 1)):
                    deficit = 0
                if deficit > 0:
                    demoted = queue.demote(deficit)
                    if len(demoted):
                        backlog.append(demoted)
                        report.n_rebalanced += len(demoted)

        if inflight is None and backlog:
            dispatch_sparse(_concat(backlog))
            backlog = []

    if inflight is not None:
        harvest_sparse()

    # Terminal sparse round: leftover demotions + §V-E failure lane.
    report.n_failed = int(sum(len(f) for f in failed))
    tail_ids = _concat(backlog + failed)
    if len(tail_ids):
        # Failures ride the sparse engine but are not "sparse work" for
        # the load model; pure=False keeps them out of T₁.
        dispatch_sparse(tail_ids, pure=False)
        if inflight is not None:
            harvest_sparse()

    # Brute backstop — exactness regardless of parameter choices.
    report.n_uncertified = int(sum(len(u) for u in uncertified))
    unc = _concat(uncertified + gated)
    if len(unc):
        t0 = time.perf_counter()
        d, i = brute_fn(unc)
        report.t_brute = time.perf_counter() - t0
        final_d[unc] = np.asarray(d)[: len(unc)]
        final_i[unc] = np.asarray(i)[: len(unc)]
        source[unc] = 2

    if dense_ok_total:
        report.t2_per_query = report.t_dense / dense_ok_total
    report.t_wall = time.perf_counter() - t_start
    return final_d, final_i, source, report
