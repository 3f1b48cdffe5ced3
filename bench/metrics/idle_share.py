"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""

from bench import trace

LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    busy = ctx.mean_over_devices(trace.busy_s)
    return 100.0 * (1.0 - busy / ctx.window_s)
