"""The fused IVF-PQ list scan's share of its roofline, in %: the least
time the chip could take for the scan work of every batch in the window
(``benchmark/work.py``) over the scan kernel's device time in the trace.
Notes which peak bounds it."""

from benchmark import kernels, work


def read(ctx):
    s, lay = ctx["trace"], ctx["layout"]
    if s is None or lay is None or not ctx["window"].batches:
        return None
    t = s.seconds(kernels.SCAN)
    if not t:
        return None
    probes = work.coarse_probes(ctx["pool"], lay["centers"],
                                lay["rotation"], lay["n_probes"])
    w = work.scan_work(ctx["window"].batches, probes, lay["list_sizes"],
                       lay["dim"], lay["code_bytes"])
    least, bound = work.least_seconds(w, ctx["peak"])
    ctx["notes"]["scan_roofline"] = dict(w, least_s=least, bound=bound,
                                         kernel_s=t)
    return 100.0 * least / t
