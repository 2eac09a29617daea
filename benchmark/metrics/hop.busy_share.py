"""The fused CAGRA hop kernel's share of device busy time, in %."""

from benchmark import kernels


def read(ctx):
    s = ctx["trace"]
    if s is None or not s.busy_s:
        return None
    t = s.seconds(kernels.HOP)
    return None if t is None else 100.0 * t / s.busy_s
