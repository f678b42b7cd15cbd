"""The capacity gate's reader on hand-built calls of 8 rows each, and
its case with nothing to read."""
import types

import numpy as np
import pytest

import harness
from repro.core.hybrid import JoinStats

DIM, K = 32, 10


def call(stats, n=8):
    return harness.CallRecord(
        wall=1.0, queries=np.zeros((n, DIM), np.float32),
        ids=np.zeros((n, K), np.int32), dists=np.zeros((n, K), np.float32),
        source=np.full((n,), 2, np.int8), stats=stats, exclude_self=True,
        programs=0)


def context(*calls):
    return harness.Context(
        calls=list(calls), window_s=10.0, setup_s=1.0, build_s=1.0,
        select_eps_s=0.5, peak_bytes=0, n_corpus=1000, dim=DIM, k=K,
        device_kind="TPU v5 lite", trace=None)


def read(ctx):
    return harness.reader("sparse_gated_share")(ctx)


def test_sparse_gated_share():
    got = read(context(call(JoinStats(n_sparse_gated=6)),
                       call(JoinStats(n_sparse_gated=2))))
    assert got == pytest.approx(100 * 8 / 16)
    assert read(context(call(JoinStats()))) == 0.0


# The stats of a program without the gate, and a call with no stats.
OLD = types.SimpleNamespace(n_sparse_engine_total=8)


@pytest.mark.parametrize("ctx", [context(call(OLD)), context(call(None)),
                                 context()],
                         ids=["no-counter", "no-stats", "no-call"])
def test_nothing_to_read(ctx):
    assert read(ctx) is None
