"""Share of the traced window in which no operation ran on the device,
in the batch cells: 1 - busy / window, in %."""


def read(ctx):
    s = ctx["trace"]
    if s is None or not s.busy_s:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
