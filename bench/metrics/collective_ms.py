"""Device time per step of collective ops (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute, async halves included),
whatever scope they sit in."""


def read(ctx):
    ms = ctx.per_step_ms("collective")
    return ms if ms > 0 else None
