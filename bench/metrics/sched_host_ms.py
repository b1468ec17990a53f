"""Frontend and scheduler host time per executed window, in ms: the wall
time of each ``ElisServer.step`` call that ran a window, less the
``EngineExecutor.execute`` and predictor ``predict`` time inside it, over
the steps that began in the measured window."""


def read(rec):
    s = [t1 - t0 - ex - pr for t0, t1, ex, pr in rec.steps
         if rec.w0 <= t0 < rec.w1]
    return 1e3 * sum(s) / len(s) if s else None
