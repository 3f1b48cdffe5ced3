"""Fused reconstruct-apply megakernel's share of its roofline (its
K-worker form on a data axis of K workers)."""

from bench import trace, work

LAYER = "megakernels"
UNIT = "%"
MOVES = "tokens_per_s"
KERNEL = r"^reconstruct_apply_packed(_workers)?(\.\d+)?$"


def read(ctx):
    t = ctx.mean_over_devices(lambda ops: trace.kernel_s(ops, KERNEL))
    if not t:
        return None
    least, _ = work.least_time(*ctx.reconstruct_work, ctx.peak)
    return 100.0 * least * ctx.steps / t
