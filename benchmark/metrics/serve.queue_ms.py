"""Mean time a request waited in the serving queue before its batch was
cut, over the window: ``serving.latency.queue`` sum / count, in ms."""


def read(ctx):
    h = ctx["histograms"].get("serving.latency.queue")
    if not h or not h.get("count"):
        return None
    return 1e3 * h["sum"] / h["count"]
