"""Every cell of BENCHMARK.json, rehearsed on the CPU at a tiny size through
the same harness code, and the check's answer to planted faults and to the
lower-precision control.

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import harness

from conftest import BENCH, ROOT

SEED = 2 ** 31 + 12345          # past 32 signed bits, as the driver's are
TINY = {"config": {"n_points": 6000}, "mix": {"batch": 64, "warm_calls": 2}}
SECONDS = 1.0


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.fixture(autouse=True)
def tiny_on_cpu(monkeypatch):
    """Run the harness on the CPU, at a tiny size: the chip check passes
    any device, and each cell's configuration and mix are cut down."""
    load = harness.load_cell

    def tiny(root, workload):
        cell = load(root, workload)
        cell.config.update(TINY["config"])
        cell.mix.update(TINY["mix"])
        return cell

    import jax
    monkeypatch.setattr(harness, "devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "load_cell", tiny)


def run(cell, trace=False, control=False):
    return harness.run_cell(ROOT, cell, SEED, SECONDS, trace,
                            t_start=time.perf_counter(), control=control,
                            log=lambda msg: None)


@pytest.mark.parametrize("cell", cells())
def test_cell_rehearsal_is_correct(cell):
    r = run(cell)
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert {"setup_s", "queries_per_s", "build_s"} <= set(r["metrics"])
    assert list(r)[-1] == "compared"
    assert r["device"]["platform"] == "cpu"


def test_traced_rehearsal_reports_per_layer_metrics():
    r = run("chist68k.selfjoin-q8192", trace=True)
    assert r["correct"]
    assert {"window_compiles", "window_jit_compiles", "select_eps_s",
            "dense_assigned_share"} <= set(
        r["metrics"])
    assert "queries_per_s" not in r["metrics"]
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def half_left_out(d, i):
    d, i = d.copy(), i.copy()
    half = len(i) // 2
    d[half:], i[half:] = np.inf, -1
    return d, i


def answer_altered(d, i):
    i = i.copy()
    i[:, -1] = (i[:, -1] + 7) % TINY["config"]["n_points"]
    return d, i


def self_not_excluded(d, i):
    d, i = d.copy(), i.copy()
    d[:, 0], i[:, 0] = 0.0, np.arange(len(i))
    return d, i


@pytest.mark.parametrize("plant", [half_left_out, answer_altered,
                                   self_not_excluded])
def test_planted_fault_is_not_correct(plant, monkeypatch):
    """The timed path broken underneath: each call's answers are altered
    as the program returns them."""
    serve = harness.ProgramServer.serve

    def broken(self, queries, exclude_self):
        d, i, src, stats = serve(self, queries, exclude_self)
        return (*plant(np.asarray(d), np.asarray(i)), src, stats)

    monkeypatch.setattr(harness.ProgramServer, "serve", broken)
    r = run("chist68k.selfjoin-q8192")
    assert not r["correct"], r["compared"]


def test_bf16_control_is_not_correct():
    r = run("chist68k.selfjoin-q8192", control=True)
    assert not r["correct"], r["compared"]
    assert r["compared"]["dist_rel_err"]["value"] > r["compared"][
        "dist_rel_err"]["limit"]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "chist68k.selfjoin-q8192", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
