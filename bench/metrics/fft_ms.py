"""Milliseconds per forward transform: the whole window over the
transforms completed in it, each ending in ``block_until_ready``."""


def read(ctx):
    steps = ctx.record.get("steps")
    return 1e3 * ctx.record["window_s"] / steps if steps else None
