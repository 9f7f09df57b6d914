"""Device time per step of the codec: ops under obs.encode and obs.decode,
and the one-pass statistics kernels the program runs outside those scopes."""


def read(ctx):
    ms = ctx.per_step_ms("codec")
    return ms if ms > 0 else None
