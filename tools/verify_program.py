"""CI gate: statically verify + trace-lint the shipped BinArrayPrograms.

    python tools/verify_program.py [--json PATH] [--skip-retrace]
                                   [--mesh devices=N[,model=K]]

Runs, for each program in ``benchmarks.run.PROGRAMS`` (CNN-A,
MobileNet-B1, MobileNet-B2):

  1. ``repro.analysis.verify_program`` on the abstract compile — Mosaic
     block legality, packed widths, plan ranges, VMEM budget, stats drift
     — and how many convs take each fused-conv path (tile / row);
  2. ``repro.analysis.trace_lint.lint_execute`` on the jitted execute jaxpr
     — zero fp conv primitives, zero trace-time plan picks, no f64
     (abstract tracing: nothing executes, so MobileNet-B2 @ 224² is cheap);
  3. for CNN-A only (small enough to actually run on CPU interpret mode),
     the retrace detector across 3x repeated mixed-``m_active`` traffic;
  4. with ``--mesh devices=N[,model=K]``: plan every program onto the
     N-device mesh (K-way model parallelism, data parallelism fills the
     rest) and run ``repro.analysis.verify_mesh_plan`` over the result —
     shard structure, channel divisibility, device-local lane legality,
     per-device VMEM budgets, byte accounting.  Static only: no devices
     are touched, so an 8-device plan audits fine on a 1-CPU runner.

Prints every finding and exits 1 if any ERROR surfaced.  CI runs this in
the fast tier (.github/workflows/ci.yml).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax
import jax.numpy as jnp

from benchmarks.run import PROGRAMS
from repro import deploy, distributed
from repro.analysis import (mosaic_rules, summarize, trace_lint,
                            verify_mesh_plan, verify_program)
from repro.core.binlinear import QuantConfig
from repro.models import cnn


def _parse_mesh(spec: str) -> tuple[int, int]:
    """``devices=N[,model=K]`` -> (n_data, n_model); K must divide N."""
    fields = dict(part.split("=", 1) for part in spec.split(",") if part)
    unknown = set(fields) - {"devices", "model"}
    if unknown or "devices" not in fields:
        raise SystemExit(
            f"--mesh expects devices=N[,model=K], got {spec!r}")
    devices = int(fields["devices"])
    n_model = int(fields.get("model", 1))
    if devices < 1 or n_model < 1 or devices % n_model:
        raise SystemExit(
            f"--mesh: model={n_model} must divide devices={devices}")
    return devices // n_model, n_model


def _retrace_check(findings: dict) -> None:
    """Compile a real (small) CNN-A program and prove repeated traffic does
    not grow the executor's compiled-variant count."""
    qc = QuantConfig(mode="binary", M=2, K_iters=2, interpret=True)
    params = cnn.init_cnn_a(jax.random.PRNGKey(0))
    program = deploy.compile(cnn.binarize_cnn_a(params, qc), "cnn_a", qc,
                             (2, 48, 48, 3), verify=True)
    x = jnp.ones((2, 48, 48, 3), jnp.float32)
    fs = trace_lint.retrace_findings(
        program, x, schedules=(None, 1), repeats=3, interpret=True)
    findings["cnn_a_retrace"] = [f.as_dict() for f in fs]
    for f in fs:
        print(f"  {f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also dump all findings as JSON")
    ap.add_argument("--skip-retrace", action="store_true",
                    help="skip the (executing) CNN-A retrace check")
    ap.add_argument("--mesh", default="", metavar="devices=N[,model=K]",
                    help="also plan each program onto this mesh and audit "
                         "the MeshPlan (verify_mesh_plan)")
    args = ap.parse_args()
    mesh = _parse_mesh(args.mesh) if args.mesh else None

    qc = QuantConfig(mode="binary", M=2, K_iters=1)
    doc: dict = {"rules": sorted(mosaic_rules.RULES)}
    n_errors = 0
    for key, (arch, shape, kw) in PROGRAMS.items():
        prog = deploy.abstract_program(arch, qc, shape, **kw)
        static = verify_program(prog)
        traced = trace_lint.lint_execute(prog, interpret=True)
        fs = static + traced
        summ = summarize(fs)
        n_errors += summ["errors"]
        doc[key] = {"summary": summ, "findings": [f.as_dict() for f in fs]}
        doc[key]["conv_paths"] = prog.conv_paths()
        print(f"{key}: {summ['errors']} error(s), "
              f"{summ['warnings']} warning(s), "
              f"conv paths {prog.conv_paths()}")
        for f in fs:
            print(f"  {f}")
        if mesh is not None:
            n_data, n_model = mesh
            plan = distributed.plan_mesh(prog, n_data=n_data,
                                         n_model=n_model)
            mfs = verify_mesh_plan(prog, plan)
            msumm = summarize(mfs)
            n_errors += msumm["errors"]
            doc[key]["mesh"] = {
                "n_data": n_data, "n_model": n_model,
                "summary": msumm,
                "findings": [f.as_dict() for f in mfs],
                "totals": distributed.mesh_totals(prog, plan),
            }
            print(f"{key} @ mesh {n_data}x{n_model}: "
                  f"{msumm['errors']} error(s), "
                  f"{msumm['warnings']} warning(s), "
                  f"{sum(1 for s in plan.shards if s.kind == 'bd')} "
                  f"bd-sharded layer(s)")
            for f in mfs:
                print(f"  {f}")

    if not args.skip_retrace:
        print("cnn_a retrace check (3x repeated mixed-m_active traffic)")
        _retrace_check(doc)
        n_errors += len(doc["cnn_a_retrace"])

    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print(f"findings written to {args.json}")
    print(f"verify_program: {'FAIL' if n_errors else 'OK'} "
          f"({n_errors} ERROR finding(s))")
    return 1 if n_errors else 0


if __name__ == "__main__":
    sys.exit(main())
