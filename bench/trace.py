"""From a profiler trace to the numbers the per-layer readers need.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events; the reductions below work on those events alone, so the tests run
them on a small recorded trace.

* device ops: the ``XLA Ops`` line of every ``/device:...`` plane (the
  ``XLA Modules`` line where a plane has no op line), each named by its
  HLO instruction (``flash_decode.6``, ``fusion.174``: the event's HLO
  text cut at `` = ``);
* host spans: the ``TraceAnnotation`` events the harness puts around the
  program's public calls, with their ``window`` stat where they have one.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

#: the span that marks the traced window itself
WINDOW_SPAN = "bench.traced"
#: HLO control flow: these ops contain the ops of their bodies
CONTAINERS = ("while", "conditional", "call")
#: host span names the harness records (see bench/harness.py)
HOST_SPANS = (WINDOW_SPAN, "ElisServer.step", "EngineExecutor.execute",
              "BGEPredictor.predict")


@dataclass(frozen=True)
class Event:
    name: str
    start: float   # seconds on the trace's clock
    end: float
    device: str = ""
    window: Optional[int] = None

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: List[Event] = field(default_factory=list)     # device operations
    spans: List[Event] = field(default_factory=list)   # host spans
    #: the traced window on the trace's clock
    t0: float = 0.0
    t1: float = 0.0

    @property
    def devices(self) -> List[str]:
        return sorted({e.device for e in self.ops})


def op_name(hlo: str) -> str:
    """``%flash_decode.6 = bf16[...] custom-call(...)`` -> ``flash_decode.6``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def is_container(name: str) -> bool:
    return name.split(".")[0] in CONTAINERS


def find_file(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> Trace:
    """Device ops and harness spans of one ``.xplane.pb``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if line is None:
                continue
            for e in line.events:
                s = e.start_ns * 1e-9
                tr.ops.append(Event(op_name(e.name), s,
                                    s + e.duration_ns * 1e-9, plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name not in HOST_SPANS:
                        continue
                    stats = dict(e.stats)
                    s = e.start_ns * 1e-9
                    w = stats.get("window")
                    tr.spans.append(Event(
                        e.name, s, s + e.duration_ns * 1e-9, plane.name,
                        None if w is None else int(w)))
    tr.ops.sort(key=lambda e: e.start)
    tr.spans.sort(key=lambda e: e.start)
    marks = [e for e in tr.spans if e.name == WINDOW_SPAN]
    tr.spans = [e for e in tr.spans if e.name != WINDOW_SPAN]
    if marks:
        tr.t0, tr.t1 = marks[0].start, marks[0].end
    elif tr.ops:
        tr.t0, tr.t1 = tr.ops[0].start, max(e.end for e in tr.ops)
    return tr


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = tr.devices
    if not devs:
        return 0.0
    total = 0.0
    for d in devs:
        iv = clip(union([(e.start, e.end) for e in tr.ops if e.device == d]),
                  tr.t0, tr.t1)
        total += sum(e - s for s, e in iv)
    return total / len(devs)


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """Device operations by total time (seconds, averaged over devices);
    loops and calls are left out, their bodies' ops are counted."""
    agg: Dict[str, float] = {}
    for e in tr.ops:
        if is_container(e.name):
            continue
        s, t = max(e.start, tr.t0), min(e.end, tr.t1)
        if t > s:
            agg[e.name] = agg.get(e.name, 0.0) + (t - s)
    k = max(len(tr.devices), 1)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / k] for name, sec in top]


def _label(spans: Sequence[Event], t: float) -> str:
    """The host spans open at ``t``, outermost first ("none" if none)."""
    names = [e.name for e in spans if e.start <= t < e.end]
    return "/".join(names) if names else "none"


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """Idle time of the first device within the window, summed by what the
    host was doing (the spans open at each gap's midpoint), longest
    first."""
    devs = tr.devices
    if not devs:
        return []
    busy = clip(union([(e.start, e.end) for e in tr.ops
                       if e.device == devs[0]]), tr.t0, tr.t1)
    edges = [tr.t0] + [x for iv in busy for x in iv] + [tr.t1]
    agg: Dict[str, float] = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            lab = _label(tr.spans, 0.5 * (s + e))
            agg[lab] = agg.get(lab, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def kernel_time(tr: Trace, kernel: str, t0: float, t1: float) -> float:
    """Device seconds of the ops of ``kernel`` (``kernel`` or
    ``kernel.<n>``) that start in ``[t0, t1)``."""
    return sum(e.dur for e in tr.ops
               if e.name.split(".")[0] == kernel and t0 <= e.start < t1)


def window_spans(tr: Trace) -> Dict[int, Event]:
    """``EngineExecutor.execute`` spans by window index."""
    return {e.window: e for e in tr.spans
            if e.name == "EngineExecutor.execute" and e.window is not None}
