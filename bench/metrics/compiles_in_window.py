"""Kernel dispatch: backend compiles plus persistent-cache loads during
the window, as JAX's monitoring events count them."""


def read(run):
    return run.compiles_in_window
