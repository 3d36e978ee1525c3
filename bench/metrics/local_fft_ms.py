"""Device milliseconds per transform in local FFT operations (XLA fft,
the Pallas FFT kernels, DFT dots), on the slowest chip."""


def read(ctx):
    if ctx.trace is None:
        return None
    per_step = ctx.trace.per_step_max(lambda d: d.class_s("local_fft"))
    return 1e3 * per_step if per_step else None
