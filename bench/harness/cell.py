"""One run of one cell: set up, measure the window, check, report.

The traffic file names its loop, ``bench/loops/<loop>.py`` (see
``spec.loop``), which makes the cell's inputs from the seed, warms up every
shape it will use, and drives the program through the window.  Everything
else is the same for every cell, here.

The order matters: set-up ends when the window opens (``setup_s``); the
device's peak memory is read when the window closes; the program's state is
freed before the plain reference runs, so the reference never sets the
peak, and its time counts in no metric.
"""
from __future__ import annotations

import contextlib
import gc
import json
import shutil
import sys
import time
import types

import numpy as np

from harness import model, reference, spec, work

TRACE_DIR = spec.ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def logit_err(out: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap of any image's logits from the reference's, as a share of
    the largest reference logit of that image."""
    gap = np.max(np.abs(out - ref), axis=-1)
    scale = np.max(np.abs(ref), axis=-1)
    return float(np.max(gap / scale))


class CompileCounter:
    """Counts the backend compilations JAX reports inside a ``with``."""

    def __enter__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile/backend_compile"):
            self.n += 1


def _annotation(traced: bool):
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
        devices: list, peak: dict, t_start: float, interpret: bool = False,
        controls: tuple = ()) -> dict:
    """Run the cell once on ``devices`` (whose peaks are ``peak``) and
    return its result line as a dict.  Each of ``controls``, a (forward,
    binarize) pair of precisions, adds to the result under
    ``control_logit_err`` the same comparison made of the reference
    computed at those precisions, put in the program's place."""
    import jax

    from repro.deploy import executor

    config, traffic = cell.config, cell.traffic
    device = devices[0]
    m = config["M"] if traffic["m_active"] is None else int(traffic["m_active"])
    block = traffic["batch"]

    params, near = model.make_params(config, seed)
    log(f"weights left within {model.TIE_EPS} of an Algorithm 2 tie: {near}")
    program = model.build_program(config, params, block, interpret)
    loop = spec.loop(traffic["loop"])
    state = loop.prepare(program, config, traffic, seed, device)

    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    traces_before = executor.trace_entry_count()
    span = _annotation(traced)
    setup_s = time.perf_counter() - t_start
    with CompileCounter() as counter, span("window"):
        got = loop.window(state, seconds, span)
    if traced:
        jax.profiler.stop_trace()
    retraces = executor.trace_entry_count() - traces_before
    log(f"window: {got.elapsed_s:.3f} s, compiles in window {counter.n}, "
        f"executor retraces in window {retraces}")
    for k, v in got.notes.items():
        log(f"{k}: {v}")
    stats = device.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    # free the program's state before the reference runs
    del program, state
    gc.collect()

    err, control_err = np.inf, {}
    if got.answers:
        used = np.unique(np.concatenate([i for i, _ in got.answers]))
        x = got.images[used]
        ref = reference.logits(params, x, config, m, block=block)
        err = max(logit_err(out, ref[np.searchsorted(used, idx)])
                  for idx, out in got.answers)
        control_err = {
            f"{fwd_p}/{bin_p}": logit_err(reference.logits(
                params, x, config, m, fwd_p, bin_p, block=block), ref)
            for fwd_p, bin_p in controls}
    metrics = dict(got.metrics, setup_s=setup_s)
    checks = {"logit_err": {"value": err, "limit": cell.limits["logit_err"]},
              "missing": {"value": got.failed, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    info = {"platform": device.platform, "kind": device.device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak}
    units = {d["name"]: d["unit"] for d in cell.end_to_end + cell.per_layer}
    result = {"correct": bool(correct), "attempted": got.attempted,
              "failed": got.failed}
    if traced:
        from harness import trace

        tr = trace.read(str(TRACE_DIR), ("window",) + tuple(loop.SPANS))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        plane = (f"/device:TPU:{device.id}" if device.platform == "tpu"
                 else "/host:CPU")
        if plane not in tr.devices:
            raise RuntimeError(f"the trace has no plane for {device}; it "
                               f"has {sorted(tr.devices)}")
        w0, w1 = tr.window()
        info["busy_s"] = tr.busy_ns(plane) * 1e-9
        info["window_s"] = (w1 - w0) * 1e-9
        ctx = types.SimpleNamespace(
            config=config, m=m, layers=work.layers(config), peak=peak,
            trace=tr, device=plane, window=got)
        values = {}
        for metric in cell.per_layer:
            v = spec.reader(metric["name"])(ctx)
            if v is not None:
                values[metric["name"]] = v
        result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                             for k, v in values.items()}
        result["device"] = info
        result["breakdown"] = tr.breakdown(plane)
    else:
        result["metrics"] = {d["name"]: {"value": float(metrics[d["name"]]),
                                         "unit": d["unit"]}
                             for d in cell.end_to_end}
        result["device"] = info
    result["checks"] = checks
    if controls:
        result["control_logit_err"] = control_err
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return result


def dumps(result: dict) -> str:
    """The result line; a number that is not finite is written as null."""
    def clean(v):
        if isinstance(v, dict):
            return {k: clean(x) for k, x in v.items()}
        if isinstance(v, list):
            return [clean(x) for x in v]
        if isinstance(v, float) and not np.isfinite(v):
            return None
        return v

    return json.dumps(clean(result), allow_nan=False)
