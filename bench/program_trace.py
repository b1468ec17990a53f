"""The program's own spans, and the device's programs, from the profiler
trace of a traced run.

``bench/trace.py`` keeps the device operations and the harness's spans.
This module reads the same ``.xplane.pb`` for what the program itself
puts there:

* every host event named ``elis.*`` (``repro.spans``: frontend and
  scheduler, length predictor, engine), with its stats;
* the ``XLA Modules`` line of each device plane: one event per run of a
  jitted program, named after it (``jit_decode_window(...)``).

Both are on the trace's one clock, so a device gap can be laid against
the innermost program span open during it.  The readers of the per-layer
metrics reach this through :func:`of`, which finds the file whose traced
window is the one the record holds.

    python -m bench.program_trace <cell>   # the newest trace of a cell

prints the device's idle time by innermost span, the spans and device
time of each program per window.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench import registry
from bench import trace as T

#: the program's span names all start with this
PREFIX = "elis."
#: what the innermost span's name starts with, per layer
LAYERS = {"frontend and scheduler": "elis.sched.",
          "length predictor": "elis.predict",
          "engine": "elis.engine."}
#: one span per executed window
WINDOW = "elis.engine.execute"
#: the jitted programs of the engine, by the name the device trace gives
DECODE = ("decode_window",)
PREFILL = ("prefill", "prefill_chunk")
#: where ``bench.run`` leaves its traces, one directory per cell
TRACE_DIR = registry.ROOT / ".bench_out" / "trace"


@dataclass(frozen=True)
class Span:
    name: str
    start: float   # seconds on the trace's clock
    end: float
    stats: Tuple[Tuple[str, object], ...] = ()


@dataclass
class ProgramTrace:
    spans: List[Span] = field(default_factory=list)      # elis.* spans
    modules: List[T.Event] = field(default_factory=list)  # program runs
    #: the traced window, as ``bench.trace.load`` reads it
    t0: float = 0.0
    t1: float = 0.0


def program(module: str) -> str:
    """``jit_decode_window(12)`` -> ``decode_window``."""
    m = re.match(r"jit_(\w+)", module)
    return m.group(1) if m else module


def load(path: str) -> ProgramTrace:
    """Program spans and program runs of one ``.xplane.pb``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    pt = ProgramTrace()
    marks = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    pt.modules.append(T.Event(
                        e.name, s, s + e.duration_ns * 1e-9, plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    end = s + e.duration_ns * 1e-9
                    if e.name == T.WINDOW_SPAN:
                        marks.append((s, end))
                    elif e.name.startswith(PREFIX):
                        pt.spans.append(Span(e.name, s, end,
                                             tuple(dict(e.stats).items())))
    pt.spans.sort(key=lambda e: e.start)
    pt.modules.sort(key=lambda e: e.start)
    if marks:
        pt.t0, pt.t1 = min(marks)
    return pt


def _files() -> List[str]:
    files = glob.glob(os.path.join(str(TRACE_DIR), "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return sorted(files, key=os.path.getmtime, reverse=True)


_found: Dict[Tuple[float, float], Optional[ProgramTrace]] = {}


def of(rec) -> Optional[ProgramTrace]:
    """The program trace of the run whose reduced trace ``rec`` holds
    (the newest file under :data:`TRACE_DIR` with the same traced
    window), or None."""
    tr = rec.trace
    if tr is None:
        return None
    key = (tr.t0, tr.t1)
    if key not in _found:
        _found.clear()
        _found[key] = next((pt for pt in map(load, _files())
                            if (pt.t0, pt.t1) == key), None)
    return _found[key]


# --------------------------------------------------------------------------- #
# Reductions
# --------------------------------------------------------------------------- #


def windows(pt: ProgramTrace) -> int:
    """Executed windows that start in the traced window."""
    return sum(1 for s in pt.spans
               if s.name == WINDOW and pt.t0 <= s.start < pt.t1)


def count(pt: ProgramTrace, name: str) -> int:
    """Spans called ``name`` that start in the traced window."""
    return sum(1 for s in pt.spans
               if s.name == name and pt.t0 <= s.start < pt.t1)


def innermost(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """Disjoint sorted pieces of time in which some span is open, each
    with the innermost one: the open span that started last (of two that
    started together, the shorter)."""
    bounds = sorted({x for s in spans for x in (s.start, s.end)})
    order = sorted(spans, key=lambda s: (s.start, s.end))
    heap: list = []
    out = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(order) and order[i].start <= a:
            s = order[i]
            heapq.heappush(heap, (-s.start, s.end - s.start, i, s))
            i += 1
        while heap and heap[0][3].end <= a:
            heapq.heappop(heap)
        if heap:
            out.append((a, b, heap[0][3].name))
    return out


def idle_by_span(tr: T.Trace, pt: ProgramTrace) -> Dict[str, float]:
    """Idle seconds of the first device within the traced window, by the
    innermost program span open at each instant, exactly ("none" where no
    span is open)."""
    devs = tr.devices
    if not devs:
        return {}
    busy = T.clip(T.union([(e.start, e.end) for e in tr.ops
                           if e.device == devs[0]]), tr.t0, tr.t1)
    edges = [tr.t0] + [x for iv in busy for x in iv] + [tr.t1]
    segs = innermost(pt.spans)
    agg: Dict[str, float] = {}
    j = 0
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        covered = 0.0
        k = j
        while k < len(segs) and segs[k][0] < e:
            lo, hi = max(s, segs[k][0]), min(e, segs[k][1])
            if hi > lo:
                agg[segs[k][2]] = agg.get(segs[k][2], 0.0) + (hi - lo)
                covered += hi - lo
            k += 1
        if e - s > covered:
            agg["none"] = agg.get("none", 0.0) + (e - s - covered)
    return agg


def idle_ms(tr: T.Trace, pt: ProgramTrace, prefix: str) -> Optional[float]:
    """Idle ms of the first device per window during which the innermost
    program span's name starts with ``prefix``."""
    n = windows(pt)
    if n == 0 or not tr.devices:
        return None
    idle = idle_by_span(tr, pt)
    return 1e3 * sum(v for k, v in idle.items()
                     if k.startswith(prefix)) / n


def device_ms(tr: T.Trace, pt: ProgramTrace,
              programs: Sequence[str]) -> Optional[float]:
    """Device ms per window of the runs of ``programs`` on the first
    device, within the traced window."""
    n = windows(pt)
    devs = tr.devices
    if n == 0 or not devs:
        return None
    total = sum(min(e.end, pt.t1) - max(e.start, pt.t0) for e in pt.modules
                if e.device == devs[0] and program(e.name) in programs
                and e.end > pt.t0 and e.start < pt.t1)
    return 1e3 * total / n


def per_layer(rec, fn, *args) -> Optional[float]:
    """``fn(trace, program trace, *args)`` for a record, None where the
    run holds no program spans."""
    pt = of(rec)
    if pt is None or not pt.spans:
        return None
    return fn(rec.trace, pt, *args)


def report(tr: T.Trace, pt: ProgramTrace) -> dict:
    """The breakdown to read by hand: idle by innermost span and by layer,
    spans and program device time per window, module names seen."""
    n = max(windows(pt), 1)
    idle = idle_by_span(tr, pt)
    spans: Dict[str, int] = {}
    for s in pt.spans:
        if pt.t0 <= s.start < pt.t1:
            spans[s.name] = spans.get(s.name, 0) + 1
    mods: Dict[str, float] = {}
    devs = tr.devices
    for e in pt.modules:
        if devs and e.device == devs[0] and pt.t0 <= e.start < pt.t1:
            mods[program(e.name)] = mods.get(program(e.name), 0.0) + e.dur
    return {
        "windows": windows(pt),
        "stretch_s": tr.t1 - tr.t0,
        "busy_s": T.busy_s(tr),
        "idle_ms_per_window": {k: 1e3 * v / n for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        "idle_ms_per_window_by_layer": {
            layer: 1e3 * sum(v for k, v in idle.items()
                             if k.startswith(p)) / n
            for layer, p in LAYERS.items()},
        "spans_per_window": {k: v / n for k, v in sorted(spans.items())},
        "device_ms_per_window": {k: 1e3 * v / n for k, v in sorted(
            mods.items(), key=lambda kv: -kv[1])},
        "module_names": sorted({e.name for e in pt.modules})[:40],
    }


def main(argv=None) -> int:
    import json

    cell = (argv or sys.argv[1:])[0]
    path = T.find_file(str(TRACE_DIR / cell))
    tr, pt = T.load(path), load(path)
    print(json.dumps(report(tr, pt), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
