"""Device milliseconds per transform of collective time during which no
other operation runs on that chip, on the slowest chip. Nothing is read
where no collective ran."""


def read(ctx):
    if ctx.trace is None:
        return None
    if not any(d.class_s("exchange") for d in ctx.trace.devices):
        return None
    per_step = ctx.trace.per_step_max(lambda d: d.exposed_exchange_s)
    return None if per_step is None else 1e3 * per_step
