"""Set-up seconds: process start to the window's start (loading, weights,
planning, compiling, warm-up)."""


def read(run):
    return run.setup_s
