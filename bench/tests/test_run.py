"""The harness end to end on the CPU, at a tiny size, in interpret mode.

The look for a chip is skipped (``cell.run`` is called with the CPU device),
and everything after it runs as on the chip: set-up, the window, the
comparison with the plain reference, and the result line.  Then the timed
path is broken underneath and ``correct`` has to come out false, once for
each fault an inference cell can have: an answer altered where it is
produced, and half of the batch left out.  ``bench/run.py`` itself refuses
to run without a TPU.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from harness import cell, spec

TINY = json.loads((spec.BENCH / "tests" / "data" /
                   "tiny_mobilenet.json").read_text())
PEAK = {"flops": 1e12, "bytes_per_s": 1e11}
TRAFFIC = {"loop": "closed", "batch": 4, "pool_batches": 2,
           "m_active": None}
SEED = 2**31 + 77
E2E = ["images_per_s", "setup_s"]
PER_LAYER = ["binary_conv_roofline", "binary_dwconv_roofline",
             "binary_matmul_roofline", "mfu_pct", "device_idle_pct.offline"]


def tiny_cell():
    limits = spec.load("b2_offline_b64").limits
    return spec.Cell({"name": "tiny_offline", "chips": 1}, TINY, TRAFFIC,
                     limits, [{"name": n, "unit": "u"} for n in E2E],
                     [{"name": n, "unit": "u"} for n in PER_LAYER])


def run(traced=False, seconds=1.0, **kw):
    return cell.run(tiny_cell(), SEED, seconds, traced,
                    devices=jax.devices(), peak=PEAK,
                    t_start=time.perf_counter(), interpret=True, **kw)


def test_rehearsal_is_correct():
    result = run()
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    names = {m["name"] for m in tiny_cell().end_to_end}
    assert set(result["metrics"]) == names
    assert list(result)[-1] == "checks"
    line = json.loads(cell.dumps(result))
    assert line["device"]["platform"] == "cpu"


def test_traced_rehearsal():
    result = run(traced=True)
    assert result["correct"]
    device = result["device"]
    assert 0 < device["busy_s"] <= device["window_s"]
    assert result["breakdown"]["device_ops"]
    assert set(result["metrics"]) <= {m["name"]
                                      for m in tiny_cell().per_layer}
    for v in result["metrics"].values():
        assert v["value"] >= 0


def alter_answer(execute):
    """The first image's answer comes out with its classes in reverse
    order."""
    def broken(program, x, *args, **kw):
        y = np.asarray(execute(program, x, *args, **kw)).copy()
        y[0] = y[0][::-1]
        return jax.numpy.asarray(y)
    return broken


def drop_half(execute):
    """Computes the back half of the batch only and answers the front half
    with it."""
    def broken(program, x, *args, **kw):
        half = x.shape[0] // 2
        y = np.asarray(execute(program, x[half:], *args, **kw))
        return jax.numpy.asarray(np.concatenate([y, y])[:x.shape[0]])
    return broken


@pytest.mark.parametrize("fault", [alter_answer, drop_half],
                         ids=["answer_altered", "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro import deploy

    monkeypatch.setattr(deploy, "execute", fault(deploy.execute))
    result = run()
    assert not result["correct"]
    assert result["checks"]["logit_err"]["value"] > \
        result["checks"]["logit_err"]["limit"]


def test_control_reads_far_above_the_program():
    """The reference one precision lower in its forward pass (three bf16
    passes, XLA's HIGH, for float32 at HIGHEST), put in the program's
    place, reads at least ten times what the program reads, and over the
    limit.  The CPU ignores ``HIGH``, so the three passes are spelled out
    here (``bf16x3``); on the chip the control is ``HIGH`` itself."""
    result = run(seconds=0.5, controls=(("bf16x3", "highest"),))
    assert result["correct"]
    control = result["control_logit_err"]["bf16x3/highest"]
    assert control >= 10 * result["checks"]["logit_err"]["value"]
    assert control > result["checks"]["logit_err"]["limit"]


def _run_py(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(script), "--workload", "b2_offline_b64",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_tpu():
    r = _run_py(spec.ROOT, spec.BENCH / "run.py")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_fails_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ runs nothing."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(tmp_path, tmp_path / "bench" / "run.py")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
