"""Coding engine: device calls (``engine.device_dispatches``) made during
the traffic window, per op served in it."""


def read(run):
    w = run.windows
    if not len(w):
        return None
    return float((w[-1, 4] - run.dispatches_at_open) / w[:, 2].sum())
