"""Seconds from the process's start to the start of the window."""


def read(ctx):
    return ctx.timings["setup_s"]
