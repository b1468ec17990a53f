"""Device-to-host reads of the engine per executed window: the
``elis.engine.sync`` spans (one per read, counted by the engine as
``host_syncs``) that start in the traced stretch, over the windows that
start in it."""

from bench import program_trace as P


def _per_window(tr, pt):
    n = P.windows(pt)
    return P.count(pt, "elis.engine.sync") / n if n else None


def read(rec):
    return P.per_layer(rec, _per_window)
