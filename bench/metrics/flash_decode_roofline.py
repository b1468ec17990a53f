"""The Pallas flash-decode kernel's share of its roofline, in %: over the
windows inside the traced stretch, the least time its calls need (the
larger of FLOPs / peak and needed bytes / bandwidth, where the needed KV
is each decoding row's own length; bench/flops.py) over the device time
of the kernel's events in those windows."""

from bench import trace as T

#: the name the kernel's ops carry in the device trace (the HLO
#: custom-call ``flash_decode.<n>``; found in a traced run, PR 12)
KERNEL = "flash_decode"


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    spans = T.window_spans(tr)
    least = took = 0.0
    for i, w in enumerate(rec.windows):
        span = spans.get(i)
        if span is None or not w["traced"]:
            continue
        t = T.kernel_time(tr, KERNEL, span.start, span.end)
        if t > 0:
            least += w["kernel_least_s"]
            took += t
    return 100.0 * least / took if took > 0 else None
