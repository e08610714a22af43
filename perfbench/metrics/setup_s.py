"""Set-up: process start to the window's opening (device start, weights,
compilation or compile-cache reads, warm-up)."""


def read(run):
    return run.setup_s
