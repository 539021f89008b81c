"""Set-up: process start to the window's start (load, warm-up, compiles
or compile-cache loads)."""


def read(run):
    return run.setup_s
