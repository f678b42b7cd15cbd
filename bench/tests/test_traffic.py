"""The traffic generator draws every call fresh from the seed, and keeps
the warm-up's draw apart from the window's."""
import numpy as np
import pytest

import traffic

SEED = 2 ** 31 + 777
CONFIG = {"generator": {"cluster_sigma": 0.03}}
CORPUS = np.random.default_rng(0).uniform(0, 1, (500, 6)).astype(np.float32)
SCALE = np.ones(6, np.float32)


def call(mix, draw, j, seed=SEED):
    return traffic.make_call(mix, CONFIG, CORPUS, SCALE, seed, draw, j)


@pytest.mark.parametrize("kind", ["corpus_jitter", "background"])
def test_foreign_calls_are_fresh_and_repeatable(kind):
    mix = {"queries": kind, "batch": 32, "jitter_sigma_frac": 0.25}
    first = call(mix, traffic.WINDOW, 0)
    assert first.queries.shape == (32, 6)
    assert first.queries.dtype == np.float32 and not first.exclude_self
    np.testing.assert_array_equal(first.queries,
                                  call(mix, traffic.WINDOW, 0).queries)
    for other in (call(mix, traffic.WINDOW, 1), call(mix, traffic.WARM, 0),
                  call(mix, traffic.WINDOW, 0, seed=SEED + 1)):
        assert not np.array_equal(first.queries, other.queries)


def test_self_prefix_joins_the_prefix():
    mix = {"queries": "self_prefix", "batch": 40}
    c = call(mix, traffic.WINDOW, 3)
    np.testing.assert_array_equal(c.queries, CORPUS[:40])
    assert c.exclude_self


def test_unknown_kind_and_oversized_prefix_are_refused():
    with pytest.raises(ValueError, match="unknown query kind"):
        traffic.check({"queries": "zipf", "batch": 4}, CORPUS)
    with pytest.raises(ValueError, match="self_prefix batch"):
        traffic.check({"queries": "self_prefix", "batch": 501}, CORPUS)
