"""The serving path's named spans in a profiler trace: a tiny engine driven
through ``ElisServer`` -> ``EngineExecutor`` under ``jax.profiler.trace``,
once with one-shot prefill and once with chunked prefill and swap
preemption.  Every span the path reaches is one of ``repro.spans.NAMES``,
spans nest as the layers call each other, every device-to-host read of the
engine is one ``elis.engine.sync`` span and one count of ``host_syncs``,
and the engine's jitted programs carry their names into the lowered
module."""
import glob
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (BGEPredictor, ElisServer, FrontendConfig, Job,
                        PredictorConfig, PreemptionConfig, Request,
                        SchedulerConfig)
from repro.core.predictor import LengthPrediction
from repro.engine import EngineConfig, EngineExecutor, InferenceEngine
from repro.models import init_params
from repro.models import transformer as T
from repro.models.encoder import EncoderArchConfig
from repro.spans import NAMES


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen2-1.5b").reduced()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int
    stats: dict


def _spans(log_dir):
    """The ``elis.*`` host events of the profiler file under ``log_dir``."""
    [path] = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    return [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("elis.")]


class TrueLengthBGE(BGEPredictor):
    """The BGE predictor's whole path (pad, encoder, read-back), but each
    job's true remaining length as its prediction, so that ISRTF preempts
    exactly where the test wants."""

    def _prediction(self, job, mean):
        return LengthPrediction(mean=float(job.true_remaining))


def _predictor():
    return TrueLengthBGE(PredictorConfig(
        encoder=EncoderArchConfig(d_model=32, n_heads=2, n_layers=1,
                                  d_ff=64, max_len=64),
        n_fc_layers=2, fc_hidden=32, max_len=64))


def _serve(model, tmp_path, *, chunk, preemption):
    """Serve two long requests and, a moment later, a short one that
    preempts one of them, under the profiler; return the program's spans,
    the engine's read count over the run, and the executor."""
    cfg, params = model
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=128, max_output=64, eos_id=-1,
        respect_job_max=True))
    ex = EngineExecutor({0: eng})
    srv = ElisServer(FrontendConfig(
        n_nodes=1,
        scheduler=SchedulerConfig(policy="isrtf", window=4, batch_size=2,
                                  prefill_chunk=chunk),
        preemption=preemption), _predictor(), ex)
    for i, (t, n) in enumerate([(0.0, 24), (0.0, 24), (1e-6, 3)]):
        srv.submit(Request(prompt=f"p{i}", arrival_time=t, request_id=i,
                           prompt_tokens=[11 + (7 * i + k) % 60
                                          for k in range(10 + 5 * i)],
                           true_output_len=n))
    before = ex.counters()["host_syncs"]
    with jax.profiler.trace(str(tmp_path)):
        responses = srv.drain()
    assert all(r.ok for r in responses) and len(responses) == 3
    return _spans(str(tmp_path)), ex.counters()["host_syncs"] - before, ex


def _inside(spans, inner, outer):
    outs = [s for s in spans if s.name == outer]
    ins = [s for s in spans if s.name == inner]
    return bool(ins) and all(
        any(o.start <= s.start and s.end <= o.end for o in outs)
        for s in ins)


COMMON = {"elis.sched.arrival", "elis.sched.form_batch", "elis.sched.score",
          "elis.sched.preempt", "elis.sched.fill", "elis.sched.apply",
          "elis.predict", "elis.predict.pad", "elis.predict.sync",
          "elis.predict.post", "elis.engine.execute", "elis.engine.admit",
          "elis.engine.decode", "elis.engine.gather",
          "elis.engine.decode_run", "elis.engine.scatter",
          "elis.engine.emit", "elis.engine.len_write", "elis.engine.sync"}


@pytest.mark.parametrize("path", ["one_shot", "chunked_swap"])
def test_spans_of_the_serving_path(model, tmp_path, path):
    if path == "one_shot":
        spans, syncs, ex = _serve(
            model, tmp_path, chunk=None,
            preemption=PreemptionConfig(enabled=False))
        want = COMMON | {"elis.engine.alloc", "elis.engine.prefill"}
    else:
        spans, syncs, ex = _serve(
            model, tmp_path, chunk=8,
            preemption=PreemptionConfig(policy="swap", margin=1.0,
                                        max_fraction=1.0))
        want = COMMON | {"elis.engine.chunk", "elis.engine.offload",
                         "elis.engine.restore"}
        c = ex.counters()
        assert c["swapouts"] >= 1 and c["chunk_dispatches"] >= 1
    names = {s.name for s in spans}
    assert names <= set(NAMES)
    assert want <= names, sorted(want - names)
    assert _inside(spans, "elis.predict", "elis.sched.score")
    assert _inside(spans, "elis.sched.score", "elis.sched.form_batch")
    assert _inside(spans, "elis.predict.sync", "elis.predict")
    assert _inside(spans, "elis.engine.decode", "elis.engine.execute")
    assert _inside(spans, "elis.engine.decode_run", "elis.engine.decode")
    # every device-to-host read is a span and a count
    assert syncs > 0
    assert sum(1 for s in spans if s.name == "elis.engine.sync") == syncs
    windows = [s for s in spans if s.name == "elis.engine.execute"]
    assert len(windows) == ex.counters()["windows_executed"]
    assert all(s.stats["batch"] >= 1 for s in windows)


def test_host_syncs_per_node_and_in_aggregate(model):
    cfg, params = model
    engines = {n: InferenceEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=64, max_output=8, eos_id=-1)) for n in (0, 1)}
    ex = EngineExecutor(engines)
    job = Job(job_id=0, prompt="p", prompt_tokens=[11, 12, 13],
              arrival_time=0.0)
    ex.execute(0, [job], 4, 0.0)
    per = ex.node_counters()
    # one-shot admission reads the prefill logits, a decode window reads
    # the lengths before and after and the tokens
    assert per[0]["host_syncs"] == 4 and per[1]["host_syncs"] == 0
    assert ex.counters()["host_syncs"] == 4


def test_programs_carry_their_names(model):
    cfg, params = model
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=64, max_output=8, eos_id=-1))
    assert "@jit_decode_window" in eng.lower_decode_window(4, 2).as_text()
    toks = jnp.zeros((1, 16), jnp.int32)
    cache1 = T.init_cache(cfg, 1, 64)
    one = jnp.zeros((1,), jnp.int32)
    assert "@jit_prefill " in eng._prefill.lower(
        eng.params, toks, cache1, one).as_text()
    assert "@jit_prefill_chunk " in eng._chunk_fn(16).lower(
        eng.params, toks, cache1, one, one).as_text()
    np.testing.assert_array_equal(eng.prefill_logits([11, 12, 13]).shape,
                                  (cfg.vocab_size,))
