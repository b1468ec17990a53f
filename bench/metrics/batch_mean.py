"""Mean number of jobs per executed window (the batch the scheduler formed
and passed to ``EngineExecutor.execute``), over the windows that began in
the measured window."""


def read(rec):
    s = [w["batch"] for w in rec.windows if rec.w0 <= w["t0"] < rec.w1]
    return sum(s) / len(s) if s else None
