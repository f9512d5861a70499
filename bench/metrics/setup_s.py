"""Set-up: process start to the window's start (JAX start, weights,
engine compile, bucket compiles and warm-up, first traffic)."""


def read(run):
    return run.setup_s
