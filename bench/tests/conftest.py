"""Puts the benchmark's modules and the program under ``src`` on the path.

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
