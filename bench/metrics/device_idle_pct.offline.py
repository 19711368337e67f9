"""device_idle_pct.offline: share (%) of the traced window of an offline
cell in which no operation ran on the device."""
from harness.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
