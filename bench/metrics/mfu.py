"""The whole step's share of the chip's bf16 peak, in %: the model FLOPs
of the useful tokens of the windows inside the traced stretch (prefill
and decode, each token at its own position; no bucket padding, masked
cache positions or frozen slots; bench/flops.py), over the traced
stretch's seconds times the peak."""


def read(rec):
    tr = rec.trace
    if tr is None or tr.t1 <= tr.t0:
        return None
    work = sum(w["model_flops"] for w in rec.windows if w["traced"])
    if work <= 0:
        return None
    return 100.0 * work / ((tr.t1 - tr.t0) * rec.peaks["bf16_flops_per_s"])
