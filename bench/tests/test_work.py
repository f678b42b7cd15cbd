"""Kernel work and byte counts, exact for known shapes, and the peaks."""
import pytest

import work

PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def test_scan_topk_counts():
    # 128 queries x 1,000 corpus rows x 18 dims, k=16.
    flops, nbytes = work.scan_topk(128, 1000, 18, 16)
    assert flops == 2 * 18 * 128 * 1000 == 4_608_000
    assert nbytes == 4 * 18 * (128 + 1000) + 8 * 16 * 128 == 97_600


def test_roofline_picks_the_binding_bound():
    assert work.roofline_s(2e12, 1e6, PEAK) == (2.0, "compute")
    assert work.roofline_s(1e6, 3e9, PEAK) == (3.0, "memory")


def test_v5e_peaks_and_missing_device():
    peak = work.device_peak("TPU v5 lite")
    assert peak["flops_per_s"] == 1.97e14
    assert peak["hbm_bytes_per_s"] == 8.19e11
    assert "cloud.google.com/tpu/docs/v5e" in peak["source"]
    with pytest.raises(KeyError, match="no peaks"):
        work.device_peak("cpu")


def test_program_counter_counts_new_shapes():
    import jax
    import jax.numpy as jnp

    import harness
    a, b = jnp.ones(3), jnp.ones(7)
    f = jax.jit(lambda x: x * 3)
    counter = harness.ProgramCounter()
    f(a)
    f(a)                    # the same shape: no new program
    f(b)
    assert counter.count == 2
