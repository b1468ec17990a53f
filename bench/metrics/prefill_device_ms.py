"""Device time of the prefill programs per executed window, in ms: the
first device's ``XLA Modules`` events of ``jit_prefill`` (one-shot
admission) and ``jit_prefill_chunk`` (chunked prefill) within the traced
stretch (bench/program_trace.py), over the windows that start in it."""

from bench import program_trace as P


def read(rec):
    return P.per_layer(rec, P.device_ms, P.PREFILL)
