"""Engine time per executed window, in ms: wall time in
``EngineExecutor.execute`` (it ends in a host read of the tokens, so it is
synced), over the windows that began in the measured window."""


def read(rec):
    s = [w["t1"] - w["t0"] for w in rec.windows
         if rec.w0 <= w["t0"] < rec.w1]
    return 1e3 * sum(s) / len(s) if s else None
