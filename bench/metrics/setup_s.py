"""Seconds from the start of the process to the start of the window:
device start, session build, compiles or cache loads, one warm-up job."""


def read(ctx):
    return ctx["setup_s"]
