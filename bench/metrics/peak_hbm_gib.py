"""The largest ``peak_bytes_in_use`` over the cell's chips, in GiB, read
from the device's allocator as the window closes."""


def read(ctx):
    return ctx.peak_bytes / 2**30 if ctx.peak_bytes else None
