"""Tests of the benchmark's own code, on the CPU: run them with

    JAX_PLATFORMS=cpu python -m pytest bench/tests

from the root of the repository."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
