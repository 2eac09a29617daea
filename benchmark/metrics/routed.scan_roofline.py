"""The routed list scan's share of the chips' roofline, in %: the least
time one chip could take for the scan work of every batch in the window
(``benchmark/work.py``, the same counts as ``scan_roofline``) over the
scan kernels' device time summed over the cell's chips.  The trace
reduction gives a per-device mean, so the sum is that mean times the
number of devices; without that factor the share would read n_devices
times too high.  Notes which peak bounds it."""

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
    chip_s = s.n_devices * t
    ctx["notes"]["routed.scan_roofline"] = dict(
        w, least_s=least, bound=bound, kernel_s_per_device=t,
        n_devices=s.n_devices)
    return 100.0 * least / chip_s
