"""The readers of the program's own counters and compile tally, on a
hand-built context whose answers are known, and each reader's case with
nothing to read."""
import types

import numpy as np
import pytest

import harness
import work
import xplane
from repro.core.hybrid import JoinStats

DIM, K = 32, 10
V5E = "TPU v5 lite"


def call(stats):
    n = 8
    return harness.CallRecord(
        wall=1.0, queries=np.zeros((n, DIM), np.float32),
        ids=np.zeros((n, K), np.int32), dists=np.zeros((n, K), np.float32),
        source=np.full((n,), 2, np.int8), stats=stats, exclude_self=True,
        programs=0)


def context(*stats, trace=None):
    return harness.Context(
        calls=[call(s) for s in stats], window_s=10.0, setup_s=1.0,
        build_s=1.0, select_eps_s=0.5, peak_bytes=0, n_corpus=1000,
        dim=DIM, k=K, device_kind=V5E, trace=trace)


# Two calls: 128 + 64 dense rows, 32 of them failed by overflow; tiles
# of 128 and 64 rows against unions of 500 and 250 candidate rows (with
# 1,000 and 250 rows read); 64 sparse rows, 16 overflowed; 0.5 s of
# compiles in the first call.
A = JoinStats(batch_sizes=[100, 28], n_dense_overflow=32,
              dense_pairs=128 * 500, dense_cand_rows=1000,
              n_sparse_engine_total=64, n_sparse_overflow=16, t_compile=0.5)
B = JoinStats(batch_sizes=[64], dense_pairs=64 * 250, dense_cand_rows=250)
# The dense kernel ran 300 + 200 ns; the other ops are not its.
OPS = [xplane.Op("jit_dense_join_jit/%knn_stream_topk_prefetch.3", 0, 300,
                 ""),
       xplane.Op("jit_dense_join_jit/%knn_stream_topk_prefetch.3", 1000,
                 1200, ""),
       xplane.Op("jit_dense_join_jit/%fusion.88", 300, 1000, ""),
       xplane.Op("jit__brute_engine/%knn_tile_topk.10", 1200, 9000, "")]
TRACE = xplane.Summary(window_s=1e-5, busy_s=9e-6, ops=OPS, gaps=[])
# The stats of a program that keeps none of these counters.
OLD = types.SimpleNamespace(batch_sizes=[128], n_sparse_engine_total=64)


def read(name, ctx):
    return harness.reader(name)(ctx)


def test_knn_stream_roofline():
    flops = 2 * DIM * (128 * 500 + 64 * 250)
    nbytes = 4 * DIM * (1000 + 250 + 192) + 8 * K * 192
    t_min = max(flops / 1.97e14, nbytes / 8.19e11)
    assert work.roofline_s(flops, nbytes, work.device_peak(V5E))[1] == (
        "memory")
    got = read("knn_stream_roofline", context(A, B, trace=TRACE))
    assert got == pytest.approx(100 * t_min / 500e-9)


def test_dense_overflow_share():
    got = read("dense_overflow_share", context(A, B))
    assert got == pytest.approx(100 * 32 / 192)


def test_sparse_overflow_share():
    assert read("sparse_overflow_share", context(A, B)) == pytest.approx(25)


def test_window_compile_s():
    assert read("window_compile_s", context(A, B)) == pytest.approx(0.5)
    assert read("window_compile_s", context(B)) == 0.0


NO_DENSE = JoinStats(n_sparse_engine_total=64)
NO_KERNEL = xplane.Summary(window_s=1e-5, busy_s=9e-6, ops=OPS[2:], gaps=[])


@pytest.mark.parametrize("name,ctx", [
    ("knn_stream_roofline", context(A, B)),
    ("knn_stream_roofline", context(A, trace=NO_KERNEL)),
    ("knn_stream_roofline", context(NO_DENSE, trace=TRACE)),
    ("knn_stream_roofline", context(OLD, trace=TRACE)),
    ("dense_overflow_share", context(NO_DENSE)),
    ("dense_overflow_share", context(OLD)),
    ("sparse_overflow_share", context(B)),
    ("sparse_overflow_share", context(OLD)),
    ("window_compile_s", context(OLD)),
    ("window_compile_s", context(None)),
], ids=["roofline-untraced", "roofline-no-kernel-op", "roofline-no-dense-row",
        "roofline-no-counters", "dense-no-dense-row", "dense-no-counters",
        "sparse-no-sparse-row", "sparse-no-counters", "compile-no-tally",
        "compile-control"])
def test_nothing_to_read(name, ctx):
    assert read(name, ctx) is None
