"""One cell of the benchmark: build the system under test, drive its
traffic as an open loop on the wall clock, read its metrics, check what it
served.

The system under test is the program's serving path, built from its public
pieces: ``ElisServer`` -> ``ELISFrontend`` -> ``EngineExecutor`` -> one
``InferenceEngine``, with the BGE-style length predictor trained at set-up.
The harness stamps every event itself, on the wall clock, when
``ElisServer.step`` returns it, and times each request from when it was
due: the frontend's own clock is virtual (it advances by each window's
engine time), so no timestamp of the program enters a metric.
"""
from __future__ import annotations

import contextlib
import gc
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import check, flops, registry, traffic, weights

#: predictor training: a stream of its own, PR 10's budget of steps
TRAIN_SEED = 7
TRAIN_REQUESTS = 1000
TRAIN_STEPS = 150
#: iteration window of the predictor's training samples (paper: 50)
SAMPLE_WINDOW = 50
#: the predictor at the widths ``serve`` uses (launch/serve.py)
PREDICTOR = dict(d_model=128, n_heads=4, n_layers=3, d_ff=256, max_len=192,
                 n_fc_layers=8, fc_hidden=256)


def log(*a) -> None:
    print("[bench]", *a, file=sys.stderr, flush=True)


def quantile(x, q: float) -> float:
    """The q-quantile of a sample (linear between order statistics)."""
    return float(np.quantile(np.asarray(x, float), q)) if len(x) else math.nan


class CompileCount:
    """Programs compiled or loaded from the persistent cache, through
    JAX's monitoring event for backend compiles (on the chip it fires for
    cache hits too: a warm set-up counts as many as a cold one)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1


# --------------------------------------------------------------------------- #
# What a run records
# --------------------------------------------------------------------------- #


@dataclass
class Record:
    """Everything the metric readers read, on the harness's clock
    (seconds after traffic start)."""

    cfg: dict
    peaks: dict
    w0: float = 0.0                # measured window
    w1: float = 0.0
    end: float = 0.0               # when the loop stopped
    due: Dict[int, float] = field(default_factory=dict)
    first_token: Dict[int, float] = field(default_factory=dict)
    finish: Dict[int, float] = field(default_factory=dict)
    #: start of the first window that carried each request
    started: Dict[int, float] = field(default_factory=dict)
    #: (wall, tokens) of every tokens event
    tokens: List[tuple] = field(default_factory=list)
    #: ElisServer.step calls that ran a window: (t0, t1, execute_s,
    #: predict_s)
    steps: List[tuple] = field(default_factory=list)
    #: executed windows: dicts with t0, t1, batch, model_flops, kernel
    #: least time, traced
    windows: List[dict] = field(default_factory=list)
    submit_lag: List[float] = field(default_factory=list)
    compiles_in_window: int = 0
    #: reduced profiler trace of the traced stretch (``--trace 1``)
    trace: object = None

    def in_window(self, rid: int) -> bool:
        return self.w0 <= self.due[rid] < self.w1


# --------------------------------------------------------------------------- #
# Set-up
# --------------------------------------------------------------------------- #


@dataclass
class System:
    cell: registry.Cell
    seed: int
    ref: object          # the reference module
    spec: object         # its Spec of this configuration
    weights: object
    engine: object
    executor: object
    predictor: object
    server: object = None


def _serving(cell) -> dict:
    return cell.cfg["serving"]


def measured_window(tr: dict, seconds: float) -> tuple:
    """The measured window ``[w0, w1)`` after the traffic's warm-up, and
    how long its requests may drain after it (0: not at all)."""
    w0 = float(tr["warmup_s"])
    return w0, w0 + seconds, float(tr["drain_cap_s"])


def rate(cell) -> float:
    """Offered load of the cell: the traffic's load times the
    configuration's knee."""
    return cell.traffic["load"] * _serving(cell)["knee_rps"]


def build(cell: registry.Cell, seed: int) -> System:
    """Weights from the seed on the device, the engine, and the predictor
    trained from its own stream."""
    import jax
    from repro.configs.base import ModelConfig
    from repro.core import BGEPredictor, PredictorConfig
    from repro.data.dataset import StepSample, clip_step_input
    from repro.engine import EngineConfig, EngineExecutor, InferenceEngine
    from repro.models.encoder import EncoderArchConfig

    cfg, sv = cell.cfg, _serving(cell)
    ref = registry.reference(cfg["reference"])
    w = weights.make(cfg, ref, seed)
    jax.block_until_ready(w)
    mc = ModelConfig(arch_id=cfg["name"], **ref.program_kwargs(cfg))
    engine = InferenceEngine(mc, w, EngineConfig(
        max_slots=sv["slots"], max_len=sv["max_len"],
        max_output=cell.traffic["output"]["cap"], eos_id=-1,
        respect_job_max=True, attn_impl=sv["attn_impl"]))
    p = PREDICTOR
    predictor = BGEPredictor(PredictorConfig(
        encoder=EncoderArchConfig(d_model=p["d_model"], n_heads=p["n_heads"],
                                  n_layers=p["n_layers"], d_ff=p["d_ff"],
                                  max_len=p["max_len"]),
        n_fc_layers=p["n_fc_layers"], fc_hidden=p["fc_hidden"],
        max_len=p["max_len"]), seed=0)
    samples = []
    for r in traffic.training_requests(cell.traffic, TRAIN_REQUESTS,
                                       TRAIN_SEED):
        for k in range(min(8, r.max_tokens // SAMPLE_WINDOW + 1)):
            done = k * SAMPLE_WINDOW
            if r.max_tokens - done <= 0:
                break
            samples.append(StepSample(
                tokens=clip_step_input(r.prompt_tokens,
                                       r.answer_tokens[:done], p["max_len"]),
                remaining=r.max_tokens - done, step=k, request_id=r.rid))
    with contextlib.redirect_stdout(sys.stderr):
        predictor.fit(samples, num_steps=TRAIN_STEPS, batch_size=32)
    return System(cell=cell, seed=seed, ref=ref,
                  spec=ref.Spec.from_config(cfg), weights=w, engine=engine,
                  executor=EngineExecutor({0: engine}), predictor=predictor)


def _job(jid: int, n_prompt: int, max_tokens: int = 8):
    from repro.core.job import Job

    return Job(job_id=jid, prompt="warm-up", arrival_time=0.0,
               prompt_tokens=[traffic.N_SPECIAL + (7 * i) % 4000
                              for i in range(n_prompt)],
               true_output_len=max_tokens)


def _buckets(top: int, low: int = 1) -> List[int]:
    out, b = [], low
    while b < top:
        out.append(b)
        b *= 2
    return out + [top]


def warm_up(sys_: System, max_pool: int) -> None:
    """Run every shape the cell's traffic can reach once: prefill (batch,
    seq) buckets up to the slot count and the longest context, decode
    windows and the slot gather/scatter for every occupancy, and the
    predictor's (batch, seq) buckets up to the largest pool."""
    sv = _serving(sys_.cell)
    ex, jid = sys_.executor, 10 ** 9
    slots, K = sv["slots"], sv["window"]
    eng = sys_.engine.cfg

    def run(jobs):
        ex.execute(0, jobs, K, now=0.0)
        for j in jobs:
            ex.evict(0, j)

    # prefill (batch, seq) buckets: prompt + resumed output can reach
    # max_len - 1 tokens; admission alone runs the prefill program
    for s in _buckets(eng.max_len, eng.prefill_bucket):
        for b in _buckets(slots):
            jobs = [_job(jid + i, s - 1) for i in range(b)]
            sys_.engine.add_jobs(jobs)
            for j in jobs:
                ex.evict(0, j)
            jid += b
    # every occupancy: the decode window of its batch bucket, and the
    # slot gather/scatter programs, which are shaped by the job count
    for n in range(1, slots + 1):
        run([_job(jid + i, eng.prefill_bucket - 1) for i in range(n)])
        jid += n
    pml = PREDICTOR["max_len"]
    for s in sorted({min(b, pml) for b in _buckets(pml, 32)}):
        for b in _buckets(max(max_pool, 1)):
            sys_.predictor.predict([_job(jid + i, s - 2) for i in range(b)])
            jid += b


def prepare(cell: registry.Cell, seed: int, max_pool: int) -> System:
    """Build the system, warm up every shape, and put a server on it."""
    sys_ = build(cell, seed)
    warm_up(sys_, max_pool)
    sys_.server = server(sys_)
    return sys_


def server(sys_: System):
    """A fresh ``ElisServer`` over the system's executor."""
    from repro.core import (ElisServer, FrontendConfig, PreemptionConfig,
                            SchedulerConfig)

    sv = _serving(sys_.cell)
    return ElisServer(FrontendConfig(
        n_nodes=1,
        scheduler=SchedulerConfig(policy=sv["policy"], window=sv["window"],
                                  batch_size=sv["slots"],
                                  repredict_every=sv["repredict_every"]),
        preemption=PreemptionConfig(enabled=True, policy=sv["preemption"]),
        observe_in_flight=False), sys_.predictor, sys_.executor)


# --------------------------------------------------------------------------- #
# The open loop
# --------------------------------------------------------------------------- #


class Instruments:
    """Host-clock spans around the program's public calls
    (``EngineExecutor.execute``, the predictor's ``predict``), installed as
    instance attributes; with ``annotate`` each is also a
    ``jax.profiler.TraceAnnotation``."""

    def __init__(self, sys_: System, rec: Record, clock, annotate: bool):
        import jax

        self.exec_s = self.pred_s = 0.0
        self.tracing = False
        ann = jax.profiler.TraceAnnotation if annotate else None
        ex, pr = sys_.executor, sys_.predictor
        execute, predict = ex.execute, pr.predict
        cfg, peaks = rec.cfg, rec.peaks
        engine = sys_.engine
        K = _serving(sys_.cell)["window"]

        def timed_execute(node, jobs, window, now, **kw):
            idx = len(rec.windows)
            before = [(not engine.has_job(j.job_id), len(j.prompt_tokens),
                       len(j.generated)) for j in jobs]
            t0 = clock()
            traced = self.tracing
            with (ann("EngineExecutor.execute", window=idx) if ann
                  else contextlib.nullcontext()):
                res = execute(node, jobs, window, now, **kw)
            t1 = clock()
            self.exec_s += t1 - t0
            for j in jobs:
                rec.started.setdefault(j.job_id, t0)
            rec.windows.append(dict(
                t0=t0, t1=t1, batch=len(jobs),
                traced=traced and self.tracing,
                **_window_work(cfg, peaks, before, res.tokens, K)))
            return res

        def timed_predict(jobs):
            t0 = clock()
            with (ann("BGEPredictor.predict") if ann
                  else contextlib.nullcontext()):
                out = predict(jobs)
            self.pred_s += clock() - t0
            return out

        ex.execute = timed_execute
        pr.predict = timed_predict
        self._owners = (ex, pr)

    def remove(self) -> None:
        ex, pr = self._owners
        del ex.execute, pr.predict


def _window_work(cfg, peaks, jobs_before, emitted, K) -> dict:
    """Model FLOPs of one window's useful tokens, and the least time of
    its decode-attention kernel calls.

    ``jobs_before`` holds (fresh, prompt length, tokens generated) of each
    job as the window began.  A fresh job is prefilled over positions
    ``0..q0-1`` (the prompt; on a resume also all but the last generated
    token); a fresh job's first token comes from that prefill.  Every other
    emitted token came from a decode step that fed position ``q0 + s``
    and attended ``q0 + s + 1`` positions, in each layer one kernel row
    whose needed KV is that length."""
    n_layers = cfg["num_hidden_layers"]
    model = 0
    rows = []
    for (fresh, plen, gen0), toks in zip(jobs_before, emitted):
        q0 = plen + max(gen0 - 1, 0)
        steps = len(toks) - (1 if fresh and gen0 == 0 and toks else 0)
        if fresh:
            model += flops.prefill_flops(cfg, q0)
        model += sum(flops.token_flops(cfg, q0 + s + 1)
                     for s in range(steps))
        rows.append((q0, steps))
    least = 0.0
    for s in range(K):
        lens = [q0 + s + 1 for q0, steps in rows if s < steps]
        if lens:
            f_, b_ = flops.decode_attention(cfg, lens)
            least += n_layers * flops.least_time(f_, b_, peaks)
    return {"model_flops": model, "kernel_least_s": least}


def drive(sys_: System, reqs: List[traffic.Request], w0: float, w1: float,
          drain_cap: float, rec: Record, *, trace_dir: Optional[str] = None,
          trace_s: float = 0.0, compiles: Optional[CompileCount] = None
          ) -> Dict[int, object]:
    """Serve ``reqs`` on the wall clock; the measured window is
    ``[w0, w1)``.  With ``drain_cap`` > 0, keep serving until every
    request due in the window has finished or ``w1 + drain_cap``;
    otherwise stop at ``w1``.  Returns the request handles."""
    import jax
    from repro.core import Request, RequestOptions

    srv = sys_.server
    fe = srv.frontend
    t_start = time.perf_counter()

    def clock():
        return time.perf_counter() - t_start

    annotate = trace_dir is not None
    inst = Instruments(sys_, rec, clock, annotate=annotate)
    rec.w0, rec.w1 = w0, w1
    handles: Dict[int, object] = {}
    window_ids = [r.rid for r in reqs if w0 <= r.due < w1]
    pending = set(window_ids)
    i, n = 0, len(reqs)
    trace_on = trace_dir is not None and trace_s > 0
    t_trace = w1 - trace_s
    mark = None
    c0 = None
    while True:
        now = clock()
        if c0 is None and now >= w0 and compiles is not None:
            c0 = compiles.n
        if trace_on and mark is None and now >= t_trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            mark = jax.profiler.TraceAnnotation("bench.traced")
            mark.__enter__()
            inst.tracing = True
        if trace_on and mark is not None and inst.tracing and now >= w1:
            inst.tracing = False
            mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
        if now >= w1 and (drain_cap <= 0 or not pending
                          or now >= w1 + drain_cap):
            break
        while i < n and reqs[i].due <= now:
            r = reqs[i]
            handles[r.rid] = srv.submit(Request(
                prompt=r.prompt, prompt_tokens=r.prompt_tokens,
                arrival_time=srv.now, request_id=r.rid,
                options=RequestOptions(max_tokens=r.max_tokens)))
            rec.due[r.rid] = r.due
            rec.submit_lag.append(now - r.due)
            i += 1
        inst.exec_s = inst.pred_s = 0.0
        s0 = clock()
        with (jax.profiler.TraceAnnotation("ElisServer.step") if annotate
              else contextlib.nullcontext()):
            events = srv.step(now)
        t = clock()
        if inst.exec_s > 0:
            rec.steps.append((s0, t, inst.exec_s, inst.pred_s))
        for ev in events:
            if ev.kind == "tokens" and ev.chunk.tokens:
                rec.first_token.setdefault(ev.job_id, t)
                rec.tokens.append((t, len(ev.chunk.tokens)))
            elif ev.kind == "finished":
                rec.finish[ev.job_id] = t
                pending.discard(ev.job_id)
        if not events and inst.exec_s == 0:
            nxt = fe.next_event_time()
            if nxt is None or nxt > clock():
                due = reqs[i].due if i < n else clock() + 0.01
                time.sleep(max(0.0, min(due - clock(), 0.01)))
    rec.end = clock()
    inst.remove()
    if trace_on and inst.tracing:
        mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
    if compiles is not None and c0 is not None:
        rec.compiles_in_window = compiles.n - c0
    return handles


# --------------------------------------------------------------------------- #
# End-to-end metrics
# --------------------------------------------------------------------------- #


def end_to_end(rec: Record) -> Dict[str, float]:
    """Tails over every request due in the window: an unfinished request
    counts with the time it had waited when the loop stopped."""
    ids = [r for r in rec.due if rec.in_window(r)]
    jct = [rec.finish.get(r, rec.end) - rec.due[r] for r in ids]
    ttft = [rec.first_token.get(r, rec.end) - rec.due[r] for r in ids]
    toks = sum(n for t, n in rec.tokens if rec.w0 <= t < rec.w1)
    return {
        "jct_mean_s": float(np.mean(jct)) if jct else math.nan,
        "jct_p95_s": quantile(jct, 0.95),
        "ttft_p95_s": quantile(ttft, 0.95),
        "output_tokens_per_s": toks / (rec.w1 - rec.w0),
    }


# --------------------------------------------------------------------------- #
# Correctness
# --------------------------------------------------------------------------- #


def served_tokens(handles) -> Dict[int, tuple]:
    """Tokens of every finished request."""
    out = {}
    for rid, h in handles.items():
        resp = h.result()
        if resp is not None and resp.ok:
            out[rid] = tuple(resp.tokens)
    return out


def checks(sys_: System, reqs, served: Dict[int, tuple], n_sample: int,
           *, control: bool = False) -> Dict[str, dict]:
    """Each number compared, with its limit: the widest logit gap of a
    sample of finished requests (the longest among them) against the
    reference, and the finished requests whose token count differs from
    their budget."""
    budget = {r.rid: r.max_tokens for r in reqs}
    prompt = {r.rid: r.prompt_tokens for r in reqs}
    wrong = sum(1 for rid, toks in served.items()
                if len(toks) != budget[rid])
    rng = traffic._rng(sys_.seed, 3)
    pick = check.sample(served, n_sample, rng)
    gap = max((check.widest_gap(sys_.ref, sys_.weights, sys_.spec,
                                prompt[r], served[r], control=control)
               for r in pick), default=math.inf)
    limit = sys_.cell.cfg["check"]["logit_gap_limit"]
    return {
        "logit_gap": {"value": gap, "limit": limit,
                      "requests": len(pick),
                      "tokens": sum(len(served[r]) for r in pick)},
        "budget_mismatch": {"value": wrong, "limit": 0},
    }


def passed(c: Dict[str, dict]) -> bool:
    return all(v["value"] <= v["limit"] for v in c.values())


# --------------------------------------------------------------------------- #
# One run
# --------------------------------------------------------------------------- #


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def kendall_tau(sys_: System, reqs, served: Dict[int, tuple]) -> float:
    """Predicted against true remaining length, at every 50-token step of
    the finished requests (never judged: the served text is random)."""
    from repro.core.job import Job

    by = {r.rid: r for r in reqs}
    jobs, truth = [], []
    for rid, toks in sorted(served.items())[:256]:
        for k in range(0, len(toks), SAMPLE_WINDOW):
            j = Job(job_id=len(jobs), prompt="", arrival_time=0.0,
                    prompt_tokens=by[rid].prompt_tokens)
            j.generated = list(toks[:k])
            jobs.append(j)
            truth.append(len(toks) - k)
    if len(jobs) < 2:
        return math.nan
    pred = np.array([p.mean for p in sys_.predictor.predict(jobs)])
    y = np.array(truth)
    a = np.sign(pred[:, None] - pred[None, :])
    b = np.sign(y[:, None] - y[None, :])
    m = np.triu(np.ones_like(a, bool), 1)
    return float(np.sum(a[m] * b[m]) / max(np.sum(m), 1))


def run(cell: registry.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, workdir: Path) -> dict:
    """One run of a cell; returns the result object (see bench/run.py)."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCount()
    dev = device_info()
    peaks = registry.peaks(dev["kind"])
    tr = cell.traffic
    r = rate(cell)
    w0, w1, drain = measured_window(tr, seconds)
    reqs = traffic.schedule(tr, r, [w0, w1], seed)
    log(f"cell={cell.name} seed={seed} rate={r:.3f} req/s requests="
        f"{len(reqs)} window=[{w0}, {w1}) s cache={cache}")

    t0 = time.perf_counter()
    sys_ = prepare(cell, seed, max_pool=len(reqs))
    setup_s = time.perf_counter() - t_process
    log(f"setup_s={setup_s:.3f} (before build {t0 - t_process:.3f}) "
        f"compiles={compiles.n}")

    rec = Record(cfg=cell.cfg, peaks=peaks)
    t_loop = time.perf_counter()
    trace_dir = None
    if trace:
        trace_dir = str(workdir / "trace" / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    handles = drive(sys_, reqs, w0, w1, drain, rec, trace_dir=trace_dir,
                    trace_s=float(tr["trace_s"]), compiles=compiles)
    mem = memory_peak()
    log(f"loop_s={time.perf_counter() - t_loop:.3f} (drain "
        f"{rec.end - rec.w1:.3f})")
    e2e = end_to_end(rec)
    served = served_tokens(handles)
    window = [x for x in rec.due if rec.in_window(x)]
    unfinished = [x for x in window if x not in rec.finish]
    lag = np.asarray(rec.submit_lag)
    log(f"requests in window={len(window)} unfinished={len(unfinished)} "
        f"submit_lag_p50_ms={quantile(lag, 0.5) * 1e3:.3f} "
        f"submit_lag_p99_ms={quantile(lag, 0.99) * 1e3:.3f} "
        f"compiles_in_window={rec.compiles_in_window}")
    if rec.steps:
        # what a stall is made of: the windows run inside the longest step
        s0, s1, ex, pr = max(rec.steps, key=lambda s: s[1] - s[0])
        inner = [w for w in rec.windows if s0 <= w["t0"] < s1]
        log(f"longest_step_s={s1 - s0:.3f} execute_s={ex:.3f} "
            f"predict_s={pr:.3f} windows={len(inner)} batch="
            f"{[w['batch'] for w in inner]} model_flops="
            f"{sum(w['model_flops'] for w in inner):.4g}")
    log("end_to_end " + " ".join(f"{k}={v:.6g}" for k, v in e2e.items()))
    log(f"predictor_kendall_tau={kendall_tau(sys_, reqs, served):.4f} "
        "(not judged)")

    layer = {}
    if trace:
        from bench import trace as T

        rec.trace = T.load(T.find_file(trace_dir))
        for m in cell.per_layer:
            v = registry.reader(m["name"]).read(rec)
            if v is not None:
                layer[m["name"]] = {"value": v, "unit": m["unit"]}

    # free the server's state before the reference runs beside the weights
    # (the handles hold the server)
    del handles
    sys_.server = sys_.executor = sys_.engine = None
    gc.collect()
    t3 = time.perf_counter()
    c = checks(sys_, reqs, served, int(tr["check_requests"]))
    ok = passed(c) and bool(served)
    log(f"check_s={time.perf_counter() - t3:.3f}")

    if trace:
        metrics = layer
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
        if any(m["name"] == "setup_s" for m in cell.end_to_end):
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    dev["memory_peak_bytes"] = mem
    out = {"correct": ok, "attempted": len(window),
           "failed": len(unfinished) if drain > 0 else 0,
           "metrics": metrics, "device": dev}
    if trace:
        from bench import trace as T

        dev["busy_s"] = T.busy_s(rec.trace)
        dev["window_s"] = rec.trace.t1 - rec.trace.t0
        out["breakdown"] = {"device_ops": T.top_ops(rec.trace),
                            "idle_gaps": T.idle_gaps(rec.trace)}
    out["checks"] = c
    return out
