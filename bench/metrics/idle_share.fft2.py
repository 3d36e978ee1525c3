"""Percent of the traced window in which no operation ran on the
device, averaged over the chips (fft2 cells)."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_share_pct()
