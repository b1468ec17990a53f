"""Length-predictor time per executed window, in ms: wall time in the
predictor's ``predict`` (it returns host arrays, so it is synced) during
the ``ElisServer.step`` calls that ran a window in the measured window."""


def read(rec):
    s = [pr for t0, t1, ex, pr in rec.steps if rec.w0 <= t0 < rec.w1]
    return 1e3 * sum(s) / len(s) if s else None
