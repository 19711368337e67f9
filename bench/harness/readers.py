"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``.

A reader is ``read(ctx) -> float | None``; ``ctx`` holds the configuration
and its layers' work (``harness.work``), the schedule's level count ``m``,
the chip's peaks, the reduced trace of a ``--trace 1`` run
(``harness.trace``) with the name of the device's plane, and the loop's
``Window``.  A reader that finds nothing to read returns None, and the
metric is left out of the result line.
"""
from __future__ import annotations


def least_s(ctx, kind: str | None = None) -> float:
    """Least time of every forward pass the window ran, over the layers of
    ``kind`` (all layers: None)."""
    return sum(
        count * sum(layer.least_s(batch, ctx.m, ctx.peak)[0]
                    for layer in ctx.layers
                    if kind is None or layer.kind == kind)
        for batch, count in ctx.window.forward_batches.items())


def kernel_roofline(ctx, kind: str, kernel: str) -> float | None:
    """Share of its roofline, in %, that one kernel family reached over the
    traced window: the least time of every call it made (one per layer of
    ``kind`` per forward pass) over the device time of its operations (the
    trace's calls of the Pallas kernel ``kernel``)."""
    events = ctx.trace.kernel(ctx.device, kernel)
    if not events or not any(layer.kind == kind for layer in ctx.layers):
        return None
    busy = sum(e.dur_ns for e in events) * 1e-9
    return 100.0 * least_s(ctx, kind) / busy


def idle_pct(ctx) -> float:
    """Share of the traced window, in %, in which no operation ran on the
    device."""
    w0, w1 = ctx.trace.window()
    return 100.0 * (1.0 - ctx.trace.busy_ns(ctx.device) / (w1 - w0))
