"""Share of the traced window in which no operation ran on a device, in
the routed cells: 1 - busy / window, in %, with busy averaged over the
cell's chips (the trace reduction's per-device mean)."""


def read(ctx):
    s = ctx["trace"]
    if s is None or not s.busy_s:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
