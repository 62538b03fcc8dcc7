"""Seconds from the process's start to the window's start: weights, engine,
warm-up (compilation in a cold run) and the reference probe."""


def read(ctx):
    return ctx["setup_s"]
