"""Projection megakernel's share of its roofline: the least time its
work needs on this chip over its device time in the trace."""

from bench import trace, work

LAYER = "megakernels"
UNIT = "%"
MOVES = "tokens_per_s"
KERNEL = r"^project_packed(\.\d+)?$"


def read(ctx):
    t = ctx.mean_over_devices(lambda ops: trace.kernel_s(ops, KERNEL))
    if not t:
        return None
    least, _ = work.least_time(*ctx.project_work, ctx.peak)
    return 100.0 * least * ctx.steps / t
