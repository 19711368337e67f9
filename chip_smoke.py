"""Smoke test of the compiled-program path on a TPU.

Drives MobileNet-B2 (1.0x, 224x224, M=2 binary levels, random weights from
``--seed``) through the entry points a deployment uses and checks each
result:

  1. ``deploy.compile`` with the static verifier, Pallas kernels lowered
     through Mosaic (never interpret mode);
  2. ``deploy.execute`` at full M and at the cheapest §IV-D ladder rung,
     each against a plain float32 reference: the fake-quant forward
     (``cnn.mobilenet_forward``) at "highest" matmul precision;
  3. ``deploy.self_test``, the golden replay of every recorded rung;
  4. 24 requests through ``CNNService``: 16 at rung 0, 8 at the last rung,
     every answer bit-equal to ``deploy.execute`` on the served batch.

With ``--four-chips`` it runs only the sharded phase instead: the same
program under ``plan_mesh(n_data=2, n_model=2)`` through
``distributed.execute_sharded``, compared with ``deploy.execute`` on one
device.

Usage (from the repository root, on a machine with a TPU):

    python chip_smoke.py [--seed N]
    python chip_smoke.py --four-chips

The timings it prints are smoke timings from one run, not metrics.  The
last line of standard output is a JSON object naming the device; any failed
phase exits non-zero before it.  Without a TPU it exits non-zero at once.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "mobilenet"
INPUT_SHAPE = (8, 224, 224, 3)
M_LEVELS = 2
# Largest allowed max|out - ref| / max|ref| of a program's logits against
# the float32 reference.  The same comparison in Pallas interpret mode on
# the CPU (seed 0, batch 8) measured 8.2e-7 at full M and 8.1e-7 at the
# last rung; the bound leaves a margin for another summation order and no
# more.
TOLERANCE = 1e-5


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def tpu_devices(count: int):
    """The first ``count`` TPU devices; exits when JAX finds no TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU devices, found "
                 f"{len(devices)}")
    return devices


def make_params(seed: int) -> dict:
    """Seeded B2 weights, scaled as the repository's MobileNet tests scale
    them (x3, small random biases): the plain 0.1-scale init shrinks the
    logits to ~1e-12 over 28 layers, which would make any comparison
    vacuous."""
    import jax

    from repro.models import cnn

    key = jax.random.PRNGKey(seed)
    params = cnn.init_mobilenet(key)
    for i, name in enumerate(sorted(params)):
        layer = params[name]
        layer["w"] = layer["w"] * 3.0
        layer["b"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 100 + i), layer["b"].shape)
    return params


def images(seed: int, n: int):
    import jax

    return jax.random.normal(jax.random.PRNGKey(seed),
                             (n,) + INPUT_SHAPE[1:], "float32")


@functools.cache
def _reference_fn(m_active: int):
    import jax

    from repro.core.binlinear import QuantConfig
    from repro.models import cnn

    qc = QuantConfig(mode="fake_quant", M=M_LEVELS, m_active=m_active)
    return jax.jit(lambda p, x: cnn.mobilenet_forward(p, x, qc))


def reference(params: dict, x, m_active: int) -> np.ndarray:
    """Float32 fake-quant forward with ``m_active`` of the M levels in every
    layer, at "highest" matmul precision."""
    import jax

    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference_fn(m_active)(params, x))


def rel_err(y, ref: np.ndarray) -> float:
    y = np.asarray(y)
    return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))


def compile_program(params: dict):
    from repro import deploy
    from repro.core.binlinear import QuantConfig

    t0 = time.perf_counter()
    program = deploy.compile(
        params, ARCH, QuantConfig(mode="binary", M=M_LEVELS, interpret=False),
        input_shape=INPUT_SHAPE, verify=True)
    print(f"smoke timing: compile (binarize, verify, golden record) "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    check(program.interpret is False, "program compiled for interpret mode")
    return program


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    out.block_until_ready()
    return out, time.perf_counter() - t0


def phase_execute(program, params: dict, ladder, seed: int) -> None:
    from repro import deploy

    x = images(seed + 1, INPUT_SHAPE[0])
    for label, sched in (("full M", ladder[0]), ("last rung", ladder[-1])):
        y, first_s = timed(lambda: deploy.execute(program, x, sched))
        _, steady_s = timed(lambda: deploy.execute(program, x, sched))
        check(y.shape == (INPUT_SHAPE[0], 1000),
              f"execute {label}: logits shape {y.shape}")
        check(bool(np.isfinite(np.asarray(y)).all()),
              f"execute {label}: non-finite logits")
        # the fake-quant reference takes one level count for every layer
        check(len(set(sched)) == 1, f"{label} schedule {sched} is not uniform")
        err = rel_err(y, reference(params, x, sched[0]))
        print(f"execute {label} {sched[0]} level(s): rel err {err:.3e} "
              f"(tolerance {TOLERANCE:.0e}); smoke timing: first call "
              f"{first_s:.2f} s, steady batch {steady_s * 1e3:.2f} ms",
              flush=True)
        check(err <= TOLERANCE,
              f"execute {label}: rel err {err:.3e} > {TOLERANCE:.0e}")


def phase_self_test(program) -> None:
    from repro import deploy

    rungs = deploy.self_test(program)
    print(f"self_test: {rungs} rung(s) match their golden digests",
          flush=True)
    check(rungs >= 2, f"self_test checked {rungs} rung(s)")


def serve(program, rung: int, x) -> None:
    """Serve ``x`` through one CNNService pinned at ``rung``; every batch's
    answers must equal ``deploy.execute`` on the batch as served."""
    from repro import deploy
    from repro.serve_cnn import CNNService

    svc = CNNService(program, batch_size=8, selftest_every=1,
                     initial_rung=rung)
    reqs = [svc.submit(im) for im in np.asarray(x)]
    check(all(r.status == "queued" for r in reqs),
          f"rung {rung}: requests not admitted: {[r.status for r in reqs]}")
    while svc.queue:
        done = svc.step()
        ref = np.asarray(deploy.execute(program, svc.last_batch,
                                        svc.last_schedule))
        for r in done:
            check(r.status == "done",
                  f"rung {rung}: request {r.id} {r.status}: {r.error}")
            check(r.rung == rung, f"request {r.id} served at rung {r.rung}")
            check(np.array_equal(r.logits, ref[r.batch_index]),
                  f"rung {rung}: request {r.id} differs from deploy.execute")
    st = svc.stats
    bad = {k: st[k] for k in ("exec_exceptions", "nonfinite_detected",
                              "failed", "selftest_failures") if st[k]}
    check(not bad, f"rung {rung}: service counters {bad}")
    check(st["completed"] == len(reqs),
          f"rung {rung}: {st['completed']} of {len(reqs)} completed")
    print(f"CNNService rung {rung}: {st['completed']} requests done in "
          f"{st['batches']} batches, {st['selftest_runs']} self-tests, "
          f"bit-equal to deploy.execute", flush=True)


def phase_serve(program, ladder, seed: int) -> None:
    x = images(seed + 2, 24)
    serve(program, 0, x[:16])
    serve(program, len(ladder) - 1, x[16:])


def phase_sharded(program, devices, seed: int) -> None:
    """B2 on a 2x2 mesh (data x model, the pw layers bd-sharded) against
    ``deploy.execute`` on one device."""
    import jax

    from repro import deploy, distributed

    plan = distributed.plan_mesh(program, n_data=2, n_model=2)
    n_bd = sum(s.kind == "bd" for s in plan.shards)
    check(n_bd > 0, "plan_mesh bd-shards no layer")
    x = images(seed + 1, INPUT_SHAPE[0])
    y, first_s = timed(lambda: distributed.execute_sharded(program, plan, x))
    _, steady_s = timed(lambda: distributed.execute_sharded(program, plan, x))
    used = y.sharding.device_set
    check(len(used) == 4, f"sharded logits live on {len(used)} device(s)")
    ref = deploy.execute(program, jax.device_put(x, devices[0]))
    check(ref.sharding.device_set == {devices[0]},
          f"reference ran on {ref.sharding.device_set}")
    y, ref = np.asarray(y), np.asarray(ref)
    bitwise = bool(np.array_equal(y, ref))
    err = rel_err(y, ref)
    print(f"execute_sharded 2x2 ({n_bd} of {len(plan.shards)} layers "
          f"bd-sharded, logits on {len(used)} devices): bitwise equal "
          f"{bitwise}, rel err {err:.3e}; smoke timing: first call "
          f"{first_s:.2f} s, steady batch {steady_s * 1e3:.2f} ms", flush=True)
    check(err <= TOLERANCE,
          f"execute_sharded: rel err {err:.3e} > {TOLERANCE:.0e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 sharded phase, on four chips")
    args = ap.parse_args()
    count = 4 if args.four_chips else 1
    devices = tpu_devices(count)

    from repro.compile_cache import use_persistent_cache
    from repro.serve_cnn.slo import default_ladder

    print(f"compile cache: {use_persistent_cache()}", flush=True)
    params = make_params(args.seed)
    program = compile_program(params)
    if args.four_chips:
        phase_sharded(program, devices, args.seed)
    else:
        ladder = default_ladder(program)
        phase_execute(program, params, ladder, args.seed)
        phase_self_test(program)
        phase_serve(program, ladder, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
