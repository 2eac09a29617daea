"""Seconds the IVF-PQ build spent in its k-means stage, less the
compilation inside it: the library's ``ivf_pq.build.kmeans`` stage timer
(fenced on the centres it returns), on the host clock, minus the union
of the ``/jax/core/compile/*`` intervals within the stage, as ``build_s``
is taken."""

STAGE = "ivf_pq.build.kmeans"


def read(ctx):
    iv = [(s, e) for name, s, e in ctx["stages"] if name == STAGE]
    if not iv:
        return None
    return sum(e - s - ctx["compile_s"](s, e) for s, e in iv)
