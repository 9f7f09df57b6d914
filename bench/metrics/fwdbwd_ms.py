"""Device time per step of the model's forward and backward pass: every
op that is no collective, under no obs.* scope and no statistics kernel
(the sync region's unscoped bucket concatenation and split count here)."""


def read(ctx):
    ms = ctx.per_step_ms("model")
    return ms if ms > 0 else None
