"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (its configuration, traffic mix and
metrics) is looked up by name in BENCHMARK.json.  Diagnostics go to
standard error, ending with each number the correctness check compared and
its limit; the last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``.  Without a TPU, or on a TPU whose kind
is not in bench/peaks.json, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# The TPU runtime logs to /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def use_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout (the path is part of each entry's key), for every compile,
    small ones too: binarization and the reference compile op by op."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import cell as cell_run
    from harness import spec

    cell = spec.load(args.workload)
    use_cache()
    try:
        devices = spec.chips(cell.workload["chips"])
        peak = spec.peaks(devices[0].device_kind)
    except spec.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    result = cell_run.run(cell, args.seed, args.seconds, bool(args.trace),
                          devices=devices, peak=peak, t_start=T_START)
    print(cell_run.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
