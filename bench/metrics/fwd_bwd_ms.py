"""Device busy time per step outside the random-bases kernels and the
collectives: the forward and backward pass and what XLA fuses around
them."""

from bench import trace

LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s"
KERNELS = r"^(project_packed|reconstruct_apply_packed(_workers)?)(\.\d+)?$"


def read(ctx):
    t = ctx.mean_over_devices(lambda ops: trace.busy_outside_s(ops, KERNELS))
    return 1e3 * t / ctx.steps if t else None
