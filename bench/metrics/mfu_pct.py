"""mfu_pct: the whole forward pass's share (%) of the chip's bf16 peak, by
device time: 2 * fp-equivalent MACs per image (bench/harness/work.py) times
the images answered in the traced window, over the seconds in which an
operation ran on the device, over the peak FLOP/s.  Device time, not the
host's clock, so the profiler's cost on the host does not scale it; time
the device sits idle (the copy of a batch to it, the host's own work)
shows in device_idle_pct, not here."""
from harness import work


def read(ctx):
    busy_s = ctx.trace.busy_ns(ctx.device) * 1e-9
    if not ctx.window.answered_images or busy_s <= 0:
        return None
    flops = 2 * work.macs_per_image(ctx.config) * ctx.window.answered_images
    return 100.0 * flops / (busy_s * ctx.peak["flops"])
