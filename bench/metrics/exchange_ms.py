"""Device time of the collectives per step: the coordinate exchange."""

from bench import trace

LAYER = "exchange"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(ctx):
    t = ctx.mean_over_devices(
        lambda ops: trace.busy_s(trace.collectives(ops)))
    return 1e3 * t / ctx.steps if t else None
