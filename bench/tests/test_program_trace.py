"""The program-span readers: idle time by innermost program span, windows,
device-to-host reads and program device time, on hand-made events, on the
recorded v5e trace with program spans laid over it, and on a file the
profiler wrote; the existing reductions read what they read before."""
import math

import pytest

from bench import program_trace as P
from bench import registry
from bench import trace as T
from bench.metrics import (decode_device_ms, device_idle,
                           flash_decode_roofline, host_syncs,
                           idle_engine_ms, idle_predict_ms, idle_sched_ms,
                           mfu, prefill_device_ms)
from bench.tests.test_trace import _recorded

DEV = "/device:TPU:0"
NEW = (idle_sched_ms, idle_predict_ms, idle_engine_ms, host_syncs,
       decode_device_ms, prefill_device_ms)


def _hand():
    """Device busy [1, 3) and [6, 7) of [0, 10); one window whose idle gap
    [3, 6) runs across the predictor, the scheduler and the engine."""
    tr = T.Trace(t0=0.0, t1=10.0)
    tr.ops = [T.Event("fusion.1", 1.0, 3.0, DEV),
              T.Event("flash_decode.6", 6.0, 7.0, DEV),
              T.Event("fusion.9", 3.0, 9.0, "/device:TPU:1")]
    S = P.Span
    pt = P.ProgramTrace(t0=0.0, t1=10.0, spans=[
        S("elis.sched.form_batch", 0.5, 4.0),
        S("elis.sched.score", 0.8, 3.5),
        S("elis.predict", 1.0, 3.2),
        S("elis.predict.pad", 1.0, 1.5),       # starts with its parent
        S("elis.engine.execute", 4.0, 9.0, (("batch", 3),)),
        S("elis.engine.decode", 5.0, 8.0),
        S("elis.engine.sync", 5.5, 7.5),
        S("elis.engine.sync", 8.2, 8.4),
    ], modules=[
        T.Event("jit_prefill(2)", 1.0, 2.0, DEV),
        T.Event("jit_prefill_chunk(9)", 2.0, 3.0, DEV),
        T.Event("jit_decode_window(3)", 6.0, 7.0, DEV),
        T.Event("jit_decode_window(3)", 9.5, 11.0, DEV),   # runs past t1
        T.Event("jit_decode_window(3)", 6.0, 9.0, "/device:TPU:1"),
        T.Event("jit_score(1)", 0.0, 0.5, DEV),
    ])
    return tr, pt


class _Rec:
    def __init__(self, tr):
        self.trace = tr


def test_innermost_prefers_the_latest_and_shortest():
    _, pt = _hand()
    segs = {(a, b): n for a, b, n in P.innermost(pt.spans)}
    assert segs[(1.0, 1.5)] == "elis.predict.pad"
    assert segs[(1.5, 3.2)] == "elis.predict"
    assert segs[(3.2, 3.5)] == "elis.sched.score"
    assert segs[(5.5, 7.5)] == "elis.engine.sync"
    assert segs[(7.5, 8.0)] == "elis.engine.decode"
    assert (9.0, 10.0) not in segs     # no span open: not a piece


def test_idle_split_exactly_by_innermost_span():
    tr, pt = _hand()
    idle = P.idle_by_span(tr, pt)
    # [0,1): none 0.5, form_batch 0.3, score 0.2; [3,6): predict 0.2,
    # score 0.3, form_batch 0.5, execute 1.0, decode 0.5, sync 0.5;
    # [7,10): sync 0.5, decode 0.5, execute 0.8 around a sync of 0.2,
    # none 1.0
    want = {"none": 1.5, "elis.sched.form_batch": 0.8,
            "elis.sched.score": 0.5, "elis.predict": 0.2,
            "elis.engine.execute": 1.8, "elis.engine.decode": 1.0,
            "elis.engine.sync": 1.2}
    assert idle.keys() == want.keys()
    for k, v in want.items():
        assert idle[k] == pytest.approx(v), k
    assert sum(idle.values()) == pytest.approx(
        tr.t1 - tr.t0 - T.busy_s(_one_device(tr)))


def _one_device(tr):
    return T.Trace(t0=tr.t0, t1=tr.t1,
                   ops=[e for e in tr.ops if e.device == DEV])


def test_readers_on_hand_made_events(monkeypatch):
    tr, pt = _hand()
    monkeypatch.setattr(P, "of", lambda rec: pt)
    rec = _Rec(tr)
    assert P.windows(pt) == 1
    assert idle_sched_ms.read(rec) == pytest.approx(1300.0)
    assert idle_predict_ms.read(rec) == pytest.approx(200.0)
    assert idle_engine_ms.read(rec) == pytest.approx(4000.0)
    assert host_syncs.read(rec) == 2
    # the second decode run is clipped at t1, device 1's is not counted
    assert decode_device_ms.read(rec) == pytest.approx(1500.0)
    assert prefill_device_ms.read(rec) == pytest.approx(2000.0)


def test_readers_are_silent_without_program_spans(monkeypatch):
    """A program that emits no spans (the parent of this reader) gives no
    value, and raises nothing."""
    tr, pt = _hand()
    rec = _Rec(tr)
    monkeypatch.setattr(P, "of", lambda rec: P.ProgramTrace(
        t0=0.0, t1=10.0, modules=pt.modules))
    assert all(m.read(rec) is None for m in NEW)
    monkeypatch.setattr(P, "of", lambda rec: None)
    assert all(m.read(rec) is None for m in NEW)
    # spans, but no executed window in the stretch
    monkeypatch.setattr(P, "of", lambda rec: P.ProgramTrace(
        t0=0.0, t1=10.0, spans=[P.Span("elis.sched.arrival", 1.0, 2.0)]))
    assert all(m.read(rec) is None for m in NEW)


def test_program_names():
    assert P.program("jit_decode_window(12)") == "decode_window"
    assert P.program("jit_prefill_chunk") == "prefill_chunk"
    assert P.program("jit_prefill.3") == "prefill"
    assert P.program("copy.11") == "copy.11"


# --------------------------------------------------------------------------- #
# The recorded v5e trace
# --------------------------------------------------------------------------- #


def _fixture_rec():
    class Rec:
        trace = _recorded()
        peaks = registry.peaks("TPU v5 lite")
        blank = dict(traced=False, model_flops=0, kernel_least_s=0.0)
        windows = [blank] * 68 + [
            dict(traced=True, model_flops=3.0e12, kernel_least_s=1.0e-4),
            dict(traced=True, model_flops=1.0e12, kernel_least_s=2.0e-5)]
    return Rec


def _existing(rec):
    tr = rec.trace
    return (T.busy_s(tr), T.idle_gaps(tr), T.top_ops(tr),
            {k: (v.start, v.end) for k, v in T.window_spans(tr).items()},
            device_idle.read(rec), mfu.read(rec),
            flash_decode_roofline.read(rec))


#: what the reductions and readers returned on the recorded trace before
#: the program had spans
BEFORE = (
    0.004160679000000028,
    [["EngineExecutor.execute", 0.04434015999999999],
     ["BGEPredictor.predict", 0.0031271499999999675]],
    [["flash_decode.6", 0.0007623649999999871],
     ["iota_reduce_fusion.2", 0.0006197980000000047],
     ["broadcast_in_dim.1", 0.000360456000000009],
     ["bitcast_add_fusion.9", 0.00022314300000000897],
     ["fusion.145", 0.00015634899999997232],
     ["fusion.144", 0.00015606200000001236],
     ["fusion.146", 0.00015434899999997032],
     ["fusion.158", 0.00014865399999999807],
     ["flash_attention.5", 0.0001310969999999967],
     ["fusion.177", 0.0001136300000000312]],
    {68: (0.006056058999999996, 0.22763763299999998),
     69: (0.23317159299999998, 0.405163525)},
    91.94103996574411, 39.328606287412796, 13.117076465997481,
)


def _overlay(tr):
    """Program spans where the harness's spans of the recorded trace
    show each layer at work: window 68 ends in the engine's reads, the
    scheduler applies it and scores the pool (the predictor inside), and
    window 69 opens with an admission."""
    S = P.Span
    spans = [
        S("elis.engine.execute", 0.006056058999999996, 0.2276),
        S("elis.engine.decode", 0.0061, 0.2275),
        S("elis.engine.sync", 0.0062, 0.2262),
        S("elis.engine.scatter", 0.2262, 0.2268),
        S("elis.engine.sync", 0.2268, 0.2270),
        S("elis.engine.emit", 0.2270, 0.2274),
        S("elis.sched.apply", 0.2277, 0.2284),
        S("elis.sched.form_batch", 0.2285, 0.2331),
        S("elis.sched.score", 0.2286, 0.2329),
        S("elis.predict", 0.22866737299999998, 0.232797102),
        S("elis.engine.execute", 0.23317159299999998, 0.405163525),
        S("elis.engine.admit", 0.2332, 0.2350),
        S("elis.engine.prefill", 0.2333, 0.2340),
    ]
    return P.ProgramTrace(spans=spans, t0=tr.t0, t1=tr.t1)


def test_existing_reductions_read_what_they_read_before():
    assert _existing(_fixture_rec()) == BEFORE


def test_program_spans_leave_the_existing_reductions_alone(monkeypatch):
    rec = _fixture_rec()
    pt = _overlay(rec.trace)
    monkeypatch.setattr(P, "of", lambda r: pt)
    got = {m.__name__: m.read(rec) for m in NEW}
    assert _existing(rec) == BEFORE
    # the stretch holds the start of window 69 only
    assert P.windows(pt) == 1
    idle = P.idle_by_span(rec.trace, pt)
    total = rec.trace.t1 - rec.trace.t0 - T.busy_s(rec.trace)
    assert sum(idle.values()) == pytest.approx(total)
    layers = sum(got[f"bench.metrics.{n}"] for n in
                 ("idle_sched_ms", "idle_predict_ms", "idle_engine_ms"))
    assert layers == pytest.approx(1e3 * (total - idle.get("none", 0.0)))
    assert all(math.isfinite(got[f"bench.metrics.{n}"]) for n in
               ("idle_sched_ms", "idle_predict_ms", "idle_engine_ms",
                "host_syncs"))
    # the fixture keeps no module line: no program device time
    assert got["bench.metrics.decode_device_ms"] == 0.0


# --------------------------------------------------------------------------- #
# A file the profiler wrote
# --------------------------------------------------------------------------- #


def test_load_and_find_a_profiler_file(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.spans import span

    trace_dir = tmp_path / "trace" / "cell"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    f = jax.jit(lambda x: (x @ x).sum())
    f(jnp.ones((32, 32))).block_until_ready()
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for i in range(2):
            with jax.profiler.TraceAnnotation("EngineExecutor.execute",
                                              window=i):
                with span("elis.engine.execute", batch=3):
                    with span("elis.engine.sync"):
                        f(jnp.ones((32, 32))).block_until_ready()
    jax.profiler.stop_trace()
    path = T.find_file(str(trace_dir))
    tr = T.load(path)
    # the harness's reduction keeps its own spans only
    assert {e.name for e in tr.spans} == {"EngineExecutor.execute"}
    pt = P.load(path)
    assert (pt.t0, pt.t1) == (tr.t0, tr.t1)
    assert [s.name for s in pt.spans] == ["elis.engine.execute",
                                          "elis.engine.sync"] * 2
    assert dict(pt.spans[0].stats) == {"batch": 3}
    assert P.windows(pt) == 2 and P.count(pt, "elis.engine.sync") == 2
    monkeypatch.setattr(P, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(P, "_found", {})
    assert P.of(_Rec(tr)).spans == pt.spans
    assert host_syncs.read(_Rec(tr)) == 1.0
    # no device plane on the CPU: no idle or device-time reading
    assert idle_engine_ms.read(_Rec(tr)) is None
    assert decode_device_ms.read(_Rec(tr)) is None
