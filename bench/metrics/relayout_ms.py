"""Device milliseconds per transform in operations that are neither a
local FFT nor a collective (copies, transposes, twiddles), on the
slowest chip."""


def read(ctx):
    if ctx.trace is None:
        return None
    per_step = ctx.trace.per_step_max(lambda d: d.class_s("relayout"))
    return 1e3 * per_step if per_step else None
