"""Seconds from the start of the run to the opening of the window:
imports, planning, making inputs, compiling (or reading the compile
cache) and warming every shape the cell uses."""


def read(ctx):
    return ctx.setup_s
