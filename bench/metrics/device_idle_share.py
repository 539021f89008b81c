"""Device: share of the traced sub-window in which no operation ran on
the chip, 100 x (1 - busy / window)."""


def read(run):
    t = run.trace
    if t is None or t.busy_s is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
