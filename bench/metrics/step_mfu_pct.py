"""Whole step's share of the chips' bf16 peak: model FLOPs (bench/flops.py,
nothing recomputed) of the traced steps over the traced window's device
time, chips and peak."""


def read(ctx):
    if ctx.steps <= 0 or ctx.window_s <= 0:
        return None
    achieved = ctx.flops_per_step * ctx.steps / ctx.window_s / ctx.devices
    return 100.0 * achieved / ctx.peaks["bf16_flops"]
