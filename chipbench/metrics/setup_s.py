"""Set-up seconds: process start to the window's start (loading, drawing
the inputs, compiling or loading from the cache, warming up)."""


def read(run):
    return run.setup_s
