"""Device idle time inside the adapter's ``bench.search`` span, as a
share of the traced window, in %: the host's routing plan, the effective
tables' upload and the routed dispatch, holding the chips idle (idle
averaged over the cell's chips)."""


def read(ctx):
    s = ctx["trace"]
    if s is None or not s.window_s:
        return None
    return 100.0 * s.gaps.get("bench.search", 0.0) / s.window_s
