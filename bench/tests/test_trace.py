"""The trace reduction: busy union, idle share, kernel time and idle gaps
by host span, on hand-made events and on a trace recorded by the
profiler."""
import json
from pathlib import Path

import pytest

from bench import trace as T
from bench.metrics import device_idle

DATA = Path(__file__).resolve().parent / "data"


def _hand():
    dev = "/device:TPU:0"
    tr = T.Trace(t0=0.0, t1=10.0)
    tr.ops = [T.Event("fusion.1", 1.0, 3.0, dev),
              T.Event("fusion.2", 2.0, 4.0, dev),        # overlaps .1
              T.Event("flash_decode.6", 6.0, 7.0, dev),
              T.Event("fusion.1", 9.5, 11.0, dev)]       # runs past t1
    tr.spans = [T.Event("ElisServer.step", 0.5, 8.0),
                T.Event("EngineExecutor.execute", 5.0, 7.5, window=3)]
    return tr


def test_union_and_busy():
    assert T.union([(1, 3), (2, 4), (6, 7)]) == [(1, 4), (6, 7)]
    assert T.busy_s(_hand()) == pytest.approx(3.0 + 1.0 + 0.5)


def test_idle_share_reader():
    class Rec:
        trace = _hand()
    assert device_idle.read(Rec) == pytest.approx(100 * (1 - 4.5 / 10))


def test_kernel_time_and_window_spans():
    tr = _hand()
    assert T.kernel_time(tr, "flash_decode", 5.0, 7.5) == 1.0
    assert T.kernel_time(tr, "flash_decode", 0.0, 5.0) == 0.0
    assert T.kernel_time(tr, "flash", 5.0, 7.5) == 0.0
    assert T.window_spans(tr)[3].start == 5.0


def test_idle_gaps_by_host_span():
    gaps = dict(T.idle_gaps(_hand()))
    # idle [0,1): midpoint 0.5, the step has begun; [4,6): midpoint 5,
    # inside the step and the execute; [7,9.5): midpoint 8.25, no span
    assert gaps["ElisServer.step"] == pytest.approx(1.0)
    assert gaps["ElisServer.step/EngineExecutor.execute"] == \
        pytest.approx(2.0)
    assert gaps["none"] == pytest.approx(2.5)
    top = T.top_ops(_hand())
    assert top[0] == ["fusion.1", pytest.approx(2.5)]


def test_load_reads_the_profilers_file(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    f = jax.jit(lambda x: (x @ x).sum())
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for i in range(3):
            with jax.profiler.TraceAnnotation("EngineExecutor.execute",
                                              window=i):
                f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(T.find_file(str(tmp_path)))
    assert sorted(T.window_spans(tr)) == [0, 1, 2]
    assert tr.t0 <= tr.spans[0].start and tr.spans[-1].end <= tr.t1
    assert all(s.name != T.WINDOW_SPAN for s in tr.spans)


def _recorded():
    """16 ms of a traced run on one TPU v5 lite (see the file's "about")."""
    fx = json.loads((DATA / "trace_v5e.json").read_text())
    tr = T.Trace(t0=fx["t0"], t1=fx["t1"])
    tr.ops = [T.Event(n, s, e, d) for n, s, e, d in fx["ops"]]
    tr.spans = [T.Event(n, s, e, window=w) for n, s, e, w in fx["spans"]]
    return tr


def test_recorded_busy_and_idle():
    tr = _recorded()
    busy = T.busy_s(tr)
    assert 0 < busy < tr.t1 - tr.t0
    ivs = T.union([(e.start, e.end) for e in tr.ops])
    assert busy == pytest.approx(sum(min(e, tr.t1) - max(s, tr.t0)
                                     for s, e in ivs))
    gaps = dict(T.idle_gaps(tr))
    # idle time is all accounted to some host activity
    assert sum(gaps.values()) == pytest.approx(tr.t1 - tr.t0 - busy)
    # after window 68's decode the host finishes the window in execute,
    # then the predictor re-scores the pool: the device waits on both
    assert gaps["EngineExecutor.execute"] > 0.01
    assert "BGEPredictor.predict" in gaps


def test_recorded_kernel_time_and_top_ops():
    tr = _recorded()
    dec = [e for e in tr.ops if e.name.startswith("flash_decode.")]
    span = T.window_spans(tr)[68]
    assert len(dec) == 3
    assert T.kernel_time(tr, "flash_decode", span.start, span.end) == \
        pytest.approx(sum(e.dur for e in dec))
    names = [n for n, _ in T.top_ops(tr)]
    assert names and not any(T.is_container(n) for n in names)
    assert T.op_name("%while.17 = (s32[]) while(%t)") == "while.17"
