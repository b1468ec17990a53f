"""Device idle time per executed window during which the innermost open
program span is the engine's (``elis.engine.*``), in ms: idle time of the
first device in the traced stretch, laid exactly against the program's
spans (bench/program_trace.py), over the windows that start in the
stretch."""

from bench import program_trace as P


def read(rec):
    return P.per_layer(rec, P.idle_ms, P.LAYERS["engine"])
