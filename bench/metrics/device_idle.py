"""Share of the traced stretch in which no operation ran on the device,
in %: 1 - (union of the device-op intervals) / (the stretch)."""

from bench import trace as T


def read(rec):
    tr = rec.trace
    if tr is None or tr.t1 <= tr.t0 or not tr.ops:
        return None
    return 100.0 * (1.0 - T.busy_s(tr) / (tr.t1 - tr.t0))
