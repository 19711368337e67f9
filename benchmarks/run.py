"""Benchmark driver: one module per paper table + kernel + roofline.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).  With
``--json PATH`` it additionally writes the machine-readable perf trajectory:
every selected module that exports ``run_structured(quick)`` contributes
JSON-ready dicts of its *derived* metrics (VMEM/HBM bytes, MXU occupancy,
tile picks, device-call counts — no CPU wall times, which are noise), plus
the CSV rows themselves, plus a ``program`` section with the deploy
compiler's per-layer tile plans and MAC/byte stats
(``BinArrayProgram.layer_stats()`` for CNN-A and MobileNet-B1/B2) and a
``verify`` section (repro.analysis finding counts + rule coverage per
program), so future PRs can diff runtime perf, compile-time decisions, and
static-analysis cleanliness without parsing the human-oriented derived
strings.  A ``meta`` block (schema version, git sha, jax version, platform)
makes artifacts pairable: ``tools/bench_diff.py`` diffs two such documents
and fails CI on occupancy/VMEM/device-call regressions vs the committed
``BENCH_baseline.json``.  CI uploads ``BENCH_kernel.json`` next to the CSV
artifact (.github/workflows/ci.yml).

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only table2,...]
                                                [--json BENCH_kernel.json]
"""
from __future__ import annotations

import argparse
import json
import platform
import subprocess
import sys
import time
import traceback

# Version of the --json document layout.  Bump on any structural change to
# the emitted sections (modules/structured row schemas, program, verify,
# distributed) — tools/bench_diff.py refuses to compare documents whose
# schema differs, so a layout change can never masquerade as a perf change.
# v2: added the ``distributed`` section (per-mesh MeshPlan byte splits).
SCHEMA_VERSION = 2

MODULES = [
    ("table2", "benchmarks.table2_accuracy"),
    ("table3", "benchmarks.table3_throughput"),
    ("table4", "benchmarks.table4_resources"),
    ("kernel", "benchmarks.kernel_bench"),
    ("roofline", "benchmarks.roofline_bench"),
    ("serve", "benchmarks.serve_bench"),
]

# compile-time sections of the JSON artifact: per-layer tile plans, VMEM/HBM
# bytes, and MAC counts straight from BinArrayProgram.layer_stats() (abstract
# compile — jax.eval_shape, no weights computed), so BENCH_kernel.json tracks
# the deploy compiler's decisions PR over PR.
PROGRAMS = {
    "cnn_a": ("cnn_a", (8, 48, 48, 3), {}),
    "mobilenet_b1": ("mobilenet", (8, 128, 128, 3), {"width_mult": 0.5}),
    "mobilenet_b2": ("mobilenet", (8, 224, 224, 3), {}),
}


def program_section() -> dict:
    from repro import deploy
    from repro.core.binlinear import QuantConfig

    qc = QuantConfig(mode="binary", M=2, K_iters=1)
    out = {}
    for key, (arch, shape, kw) in PROGRAMS.items():
        prog = deploy.abstract_program(arch, qc, shape, **kw)
        out[key] = {"totals": prog.totals(), "layers": prog.layer_stats()}
    return out


# meshes the distributed section plans every program onto: pure data
# parallelism and the data x model split (both 8 devices, so the per-device
# numbers are directly comparable across rows)
MESHES = {
    "dp8": (8, 1),
    "dp4_mp2": (4, 2),
}


def distributed_section() -> dict:
    """Per-mesh MeshPlan accounting for every tracked program: how many
    bytes of packed weights / VMEM working set / gather traffic one device
    carries under ``plan_mesh`` (abstract compile — no weights, no devices).
    ``tools/bench_diff.py`` gates these: a planner change that grows a
    per-device working set, re-replicates previously sharded weights, or
    inflates gather traffic is a regression."""
    from repro import deploy, distributed
    from repro.core.binlinear import QuantConfig

    qc = QuantConfig(mode="binary", M=2, K_iters=1)
    out = {}
    for key, (arch, shape, kw) in PROGRAMS.items():
        prog = deploy.abstract_program(arch, qc, shape, **kw)
        out[key] = {
            mesh: distributed.mesh_totals(
                prog, distributed.plan_mesh(prog, n_data=nd, n_model=nm))
            for mesh, (nd, nm) in MESHES.items()
        }
    return out


def verify_section() -> dict:
    """Static-analysis roll-up for the JSON artifact: per-program finding
    counts from ``repro.analysis.verify_program`` + the execute-trace lint,
    plus which rules exist/fired — so BENCH_kernel.json records that the
    shipped plans are clean (tools/verify_program.py is the failing gate;
    this is the trajectory record)."""
    from repro import deploy
    from repro.analysis import mosaic_rules, summarize, trace_lint
    from repro.analysis import verify_program as _verify
    from repro.core.binlinear import QuantConfig

    qc = QuantConfig(mode="binary", M=2, K_iters=1)
    out: dict = {"rules": sorted(mosaic_rules.RULES)}
    fired: set[str] = set()
    for key, (arch, shape, kw) in PROGRAMS.items():
        prog = deploy.abstract_program(arch, qc, shape, **kw)
        findings = _verify(prog) + trace_lint.lint_execute(prog,
                                                           interpret=True)
        out[key] = summarize(findings)
        fired.update(out[key]["by_rule"])
    out["rules_fired"] = sorted(fired)
    return out


def meta_section(quick: bool, only: str) -> dict:
    """Provenance block so artifacts pair: two BENCH_*.json files are
    comparable iff their schema matches (bench_diff enforces it), and the
    git sha / jax version / platform say *what* produced each side."""
    import jax

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
    except Exception:  # noqa: BLE001 — not a checkout / git missing
        sha = "unknown"
    return {
        "schema_version": SCHEMA_VERSION,
        "git_sha": sha,
        "jax_version": jax.__version__,
        "platform": platform.platform(),
        "python_version": platform.python_version(),
        "quick": quick,
        "only": only,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write derived metrics as JSON "
                         "(e.g. BENCH_kernel.json)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()

    print("name,us_per_call,derived")
    failed = 0
    doc: dict = {"quick": args.quick, "modules": {},
                 "meta": meta_section(args.quick, args.only)}
    for key, modname in MODULES:
        if only and key not in only:
            continue
        t0 = time.time()
        try:
            import importlib

            mod = importlib.import_module(modname)
            csv_rows = []
            for name, secs, derived in mod.run(quick=args.quick):
                print(f"{name},{secs * 1e6:.0f},{derived}")
                csv_rows.append({"name": name, "us_per_call": secs * 1e6,
                                 "derived": derived})
            entry: dict = {"csv_rows": csv_rows}
            doc["modules"][key] = entry  # csv_rows survive a structured fail
            if hasattr(mod, "run_structured"):
                try:
                    entry["structured"] = mod.run_structured(quick=args.quick)
                except Exception as e:  # noqa: BLE001
                    failed += 1
                    entry["structured_error"] = f"{type(e).__name__}: {e}"
                    print(f"{key}_structured_FAILED,0,{type(e).__name__}: {e}")
                    traceback.print_exc(file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"{key}_FAILED,0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
            doc["modules"][key] = {"error": f"{type(e).__name__}: {e}"}
        print(f"{key}_total,{(time.time() - t0) * 1e6:.0f},", flush=True)
    if args.json:
        try:
            doc["program"] = program_section()
        except Exception as e:  # noqa: BLE001
            failed += 1
            doc["program"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"program_section_FAILED,0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
        try:
            doc["verify"] = verify_section()
        except Exception as e:  # noqa: BLE001
            failed += 1
            doc["verify"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"verify_section_FAILED,0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
        try:
            doc["distributed"] = distributed_section()
        except Exception as e:  # noqa: BLE001
            failed += 1
            doc["distributed"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"distributed_section_FAILED,0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print(f"json_written,0,{args.json}", file=sys.stderr)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
