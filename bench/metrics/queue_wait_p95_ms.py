"""95th percentile, over the requests due in the measured window, of the
wait from when a request was due to the start of the first window that
carried it, in ms (a request never carried counts with its wait when the
loop stopped)."""

import numpy as np


def read(rec):
    w = [rec.started.get(r, rec.end) - rec.due[r]
         for r in rec.due if rec.in_window(r)]
    return 1e3 * float(np.quantile(w, 0.95)) if w else None
