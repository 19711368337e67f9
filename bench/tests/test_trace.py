"""The reduction from a trace to metrics, on small traces whose answers are
known: one written by hand, one recorded on the CPU here, and one recorded
on a TPU v5e (``data/cnna_trace_tpu.json``, two steps of CNN-A at batch 256
cut from a ``--trace 1`` run)."""
import json
import types

import jax
import jax.numpy as jnp
import pytest

from harness import readers, spec, trace, work
from harness.trace import Event, Trace


def hand_trace():
    # window 0..100; device ops [10, 30), [20, 40) overlapping, [60, 70);
    # host: device_put 0..10, execute 10..15, fetch_logits 15..55,
    # device_put 55..60, execute 60..62, fetch_logits 62..100
    conv = "f32[8,4,4,128] custom-call(f32[8,6,6,64] %pad.1)"
    dev = [Event(f"%binary_conv2d_pallas.1 = {conv}", 10, 20),
           # its operand is a conv kernel's output: not a conv call
           Event("%fusion = f32[8] fusion(%binary_conv2d_pallas.1)", 20, 20),
           Event(f"%binary_conv2d_pallas.2 = {conv}", 60, 10),
           Event("%late = f32[8] copy(%x)", 100, 5)]
    host = [Event("window", 0, 100)] + [
        Event(n, s, d) for n, s, d in [
            ("device_put", 0, 10), ("execute", 10, 5),
            ("fetch_logits", 15, 40), ("device_put", 55, 5),
            ("execute", 60, 2), ("fetch_logits", 62, 38)]]
    return Trace(devices={"/device:TPU:0": dev}, host=host)


def test_union_and_idle():
    tr = hand_trace()
    assert trace.union_ns([(10, 30), (20, 40), (60, 70)]) == [(10, 40),
                                                              (60, 70)]
    assert tr.busy_ns("/device:TPU:0") == 40
    assert tr.idle_gaps("/device:TPU:0") == [(0, 10), (40, 60), (70, 100)]


def test_kernel_events_by_source():
    tr = hand_trace()
    assert [trace.op_name(e.name) for e in
            tr.kernel("/device:TPU:0", "binary_conv2d_pallas")] == [
        "%binary_conv2d_pallas.1", "%binary_conv2d_pallas.2"]
    assert tr.kernel("/device:TPU:0", "binary_dwconv2d_pallas") == []


def test_breakdown_attributes_gaps_to_host_spans():
    b = hand_trace().breakdown("/device:TPU:0")
    assert b["device_ops"] == [
        ["%binary_conv2d_pallas.1", pytest.approx(20e-9)],
        ["%fusion", pytest.approx(20e-9)],
        ["%binary_conv2d_pallas.2", pytest.approx(10e-9)]]
    # gap 0..10 under device_put, 40..60 mostly fetch_logits (15 of 20),
    # 70..100 under fetch_logits
    assert b["idle_gaps"] == [["fetch_logits", pytest.approx(50e-9)],
                              ["device_put", pytest.approx(10e-9)]]


def ctx_for(tr, config, steps, batch, m):
    window = types.SimpleNamespace(forward_batches={batch: steps},
                                   answered_images=steps * batch)
    return types.SimpleNamespace(
        trace=tr, device="/device:TPU:0", layers=work.layers(config), m=m,
        config=config, peak={"flops": 197e12, "bytes_per_s": 819e9},
        window=window)


def test_roofline_is_least_time_over_kernel_time():
    config = json.loads((spec.BENCH / "configs" / "cnn_a.json").read_text())
    tr = hand_trace()
    ctx = ctx_for(tr, config, 2, 256, 2)
    convs = [la for la in work.layers(config) if la.kind == "conv"]
    least = 2 * sum(la.least_s(256, 2, ctx.peak)[0] for la in convs)
    got = readers.kernel_roofline(ctx, "conv", "binary_conv2d_pallas")
    assert got == pytest.approx(100 * least / 30e-9)
    assert readers.kernel_roofline(ctx, "dwconv",
                                   "binary_dwconv2d_pallas") is None
    assert readers.idle_pct(ctx) == pytest.approx(60.0)


def test_mfu_is_forward_flops_over_device_busy_time():
    config = json.loads((spec.BENCH / "configs" / "cnn_a.json").read_text())
    ctx = ctx_for(hand_trace(), config, 2, 256, 2)
    flops = 2 * work.macs_per_image(config) * 512
    assert spec.reader("mfu_pct")(ctx) == pytest.approx(
        100 * flops / (40e-9 * 197e12))


def test_read_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("execute"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.read(str(tmp_path), ("window", "execute"))
    w0, w1 = tr.window()
    assert w1 > w0
    assert [e.name for e in tr.host].count("execute") == 3
    ops = tr.ops("/host:CPU")
    assert ops and all(w0 <= e.start_ns < w1 for e in ops)
    assert 0 < tr.busy_ns("/host:CPU") <= w1 - w0


def test_recorded_tpu_trace():
    """Two closed-loop steps of cnna_offline_b256 as the chip traced them:
    each step runs one kernel per layer, so the conv kernel appears twice
    per step and the matmul kernel three times."""
    path = spec.BENCH / "tests" / "data" / "cnna_trace_tpu.json"
    doc = json.loads(path.read_text())
    tr = Trace(devices={d: [Event(*e) for e in evs]
                        for d, evs in doc["devices"].items()},
               host=[Event(*e) for e in doc["host"]])
    dev = "/device:TPU:0"
    steps = [e for e in tr.host if e.name == "execute"]
    assert len(tr.kernel(dev, "binary_conv2d_pallas")) == 2 * len(steps)
    assert len(tr.kernel(dev, "binary_matmul_pallas")) == 3 * len(steps)
    assert tr.kernel(dev, "binary_dwconv2d_pallas") == []
    w0, w1 = tr.window()
    busy = tr.busy_ns(dev)
    gaps = tr.idle_gaps(dev)
    assert busy + sum(e - s for s, e in gaps) == pytest.approx(w1 - w0)
    config = json.loads((spec.BENCH / "configs" / "cnn_a.json").read_text())
    ctx = ctx_for(tr, config, len(steps), 256, 2)
    for kind, kernel in (("conv", "binary_conv2d_pallas"),
                         ("linear", "binary_matmul_pallas")):
        share = readers.kernel_roofline(ctx, kind, kernel)
        assert 0 < share <= 100
    assert 0 < spec.reader("mfu_pct")(ctx) <= 100
