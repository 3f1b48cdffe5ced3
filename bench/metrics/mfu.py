"""Whole step's share of the chips' peak: model FLOP per token (forward
and backward, no recomputation) times the traced window's tokens per
second, over chips times peak FLOP/s."""

LAYER = "whole step"
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    rate = ctx.steps * ctx.tokens_per_step / ctx.window_s
    return 100.0 * ctx.flops_per_token * rate / (ctx.chips
                                                 * ctx.peak["flops"])
