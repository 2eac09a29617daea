"""Share of the rows dispatched to the device that were bucket padding:
``serving.padded_rows`` / (``serving.batched_rows`` + padded), in %."""


def read(ctx):
    c = ctx["counters"]
    padded = c.get("serving.padded_rows", 0)
    rows = c.get("serving.batched_rows", 0) + padded
    if not rows:
        return None
    return 100.0 * padded / rows
