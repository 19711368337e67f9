"""Read the correctness check's two readings on the chip, in one process.

    python bench/control.py --workload <name> --seconds <s> --seeds 1 2 3 ...
        [--controls high/highest ...]

For each seed it runs the cell as ``bench/run.py`` does, for a short window,
and prints one JSON line: the program's ``logit_err`` against the float32
reference, and each control's: the reference computed at a lower
precision (``<forward>/<binarize>``; ``high``: XLA's three bf16 passes),
put in the program's place.  The limit in ``bench/limits/<workload>.json``
lies between the program's largest reading and the control's smallest.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import use_cache  # also puts bench/ and src/ on sys.path


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", default=["high/highest"])
    args = ap.parse_args()

    from harness import cell as cell_run
    from harness import spec

    cell = spec.load(args.workload)
    use_cache()
    devices = spec.chips(cell.workload["chips"])
    peak = spec.peaks(devices[0].device_kind)
    controls = tuple(tuple(c.split("/")) for c in args.controls)
    program, control = [], {c: [] for c in args.controls}
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = cell_run.run(cell, seed, args.seconds, False, devices=devices,
                         peak=peak, t_start=t0, controls=controls)
        program.append(r["checks"]["logit_err"]["value"])
        for c, v in r["control_logit_err"].items():
            control[c].append(v)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": program[-1],
                          "control": r["control_logit_err"],
                          "missing": r["checks"]["missing"]["value"],
                          "attempted": r["attempted"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "program_max": max(program),
                      "control_min": {c: min(v) for c, v in control.items()}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
