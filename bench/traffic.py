"""The one traffic generator: turns a mix's data file into the calls a run
makes.

A mix file (``bench/traffic/<mix>.json``) gives the parameters; this module
is the only code that reads them, so a new mix is a new data file.  Every
mix is a closed loop of one client: the next call starts when the previous
one has returned.  Each call's queries are drawn fresh from the seed and
the call's number, in one of two draws kept apart: the warm-up serves
``warm_calls`` calls of its own draw, so the measured window never meets a
query set that set-up has served.

Kinds of query set (``queries``):

``corpus_jitter``  foreign queries: corpus rows picked by the seed plus
                   Gaussian jitter of ``jitter_sigma_frac`` times the
                   cloud's cluster sigma in every dim;
``background``     foreign queries drawn like the cloud's background
                   component: uniform over the unit box, times the
                   cloud's per-dim scale (0.02 on its tail dims);
``self_prefix``    the first ``batch`` rows of the (shuffled) corpus, each
                   joined against the index with its own row excluded.
                   ``exclude_self`` masks by position, so a prefix is the
                   only subset the public API joins against itself: every
                   call, warm-up too, serves the same rows.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import clouds

KINDS = ("corpus_jitter", "background", "self_prefix")
WARM, WINDOW = 0, 1             # the two draws


@dataclasses.dataclass
class Call:
    """One call: ``queries`` (rows, dims) float32 and whether row i
    excludes corpus row i (``exclude_self``)."""

    queries: np.ndarray
    exclude_self: bool


def check(mix: dict, corpus: np.ndarray) -> None:
    """Refuse a mix this generator cannot draw on ``corpus``."""
    if mix["queries"] not in KINDS:
        raise ValueError(f"unknown query kind {mix['queries']!r}; one of "
                         f"{KINDS}")
    if mix["queries"] == "self_prefix" and int(mix["batch"]) > len(corpus):
        raise ValueError(f"self_prefix batch {mix['batch']} > corpus rows "
                         f"{len(corpus)}")


def make_call(mix: dict, config: dict, corpus: np.ndarray,
              scale: np.ndarray, seed: int, draw: int, j: int) -> Call:
    """Call ``j`` of ``draw`` (``WARM`` or ``WINDOW``) of ``mix`` on
    ``corpus`` (whose per-dim scale is ``scale``), the same for the same
    ``seed``."""
    batch = int(mix["batch"])
    n, d = corpus.shape
    if mix["queries"] == "self_prefix":
        return Call(corpus[:batch], True)
    rng = clouds.seed_rng(seed, 2, draw, j)
    if mix["queries"] == "corpus_jitter":
        sigma = config["generator"]["cluster_sigma"] * mix[
            "jitter_sigma_frac"]
        rows = rng.choice(n, batch, replace=False)
        q = corpus[rows] + rng.normal(0.0, sigma, (batch, d))
    else:
        q = rng.uniform(0.0, 1.0, (batch, d)) * scale
    return Call(q.astype(np.float32), False)
