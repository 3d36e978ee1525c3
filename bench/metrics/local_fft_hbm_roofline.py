"""Share of the HBM roofline that the local FFT reaches: the least time
the transform's passes need at the chip's published HBM bandwidth
(bytes from the shape, ``work.hbm_bytes_per_chip``) over the measured
local FFT time per transform on the slowest chip."""

from work import hbm_roofline_s


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    measured = ctx.trace.per_step_max(lambda d: d.class_s("local_fft"))
    if not measured:
        return None
    cfg = ctx.config
    least = hbm_roofline_s(cfg["shape"], cfg["dtype"], ctx.chips, ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / measured
