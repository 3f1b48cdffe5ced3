"""Collective time per step during which no other operation runs on
that device: the part of the exchange the step waits for."""

from bench import trace

LAYER = "exchange"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(ctx):
    if not ctx.mean_over_devices(
            lambda ops: trace.busy_s(trace.collectives(ops))):
        return None
    return 1e3 * ctx.mean_over_devices(trace.exposed_s) / ctx.steps
