"""Live JAX engine: greedy exactness, windows, preemption resume, the
fast path (batched bucketed prefill, masked/compacted decode, Pallas
decode attention), and slot-bookkeeping properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core import Job
from repro.engine import EngineConfig, EngineExecutor, InferenceEngine, SamplerConfig
from repro.models import forward, init_params


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def greedy_reference(cfg, params, prompt, n):
    """Naive greedy decode via repeated full forward (the oracle)."""
    toks = list(prompt)
    out = []
    for _ in range(n):
        logits, _ = forward(params, cfg, {"tokens": jnp.asarray([toks])})
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def test_engine_greedy_matches_forward(setup):
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=128, max_output=64, eos_id=-1,
        sampler=SamplerConfig(temperature=0.0)))
    job = Job(job_id=0, prompt="x", prompt_tokens=[11, 22, 33, 44],
              arrival_time=0.0)
    toks, fin = eng.run_window([job], 10)
    want = greedy_reference(cfg, params, [11, 22, 33, 44], 10)
    assert toks[0] == want


def test_engine_windows_continue_exactly(setup):
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=128, max_output=64, eos_id=-1))
    job = Job(job_id=1, prompt="x", prompt_tokens=[5, 6, 7], arrival_time=0.0)
    t1, _ = eng.run_window([job], 6)
    job.generated.extend(t1[0])
    t2, _ = eng.run_window([job], 6)
    want = greedy_reference(cfg, params, [5, 6, 7], 12)
    assert t1[0] + t2[0] == want


def test_preempt_resume_is_exact(setup):
    """Evict + recompute-resume must continue the identical greedy stream."""
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=1, max_len=128, max_output=64, eos_id=-1))
    job = Job(job_id=2, prompt="x", prompt_tokens=[9, 8, 7], arrival_time=0.0)
    t1, _ = eng.run_window([job], 5)
    job.generated.extend(t1[0])
    eng.evict_job(job.job_id)          # preemption
    assert eng.free_slots() == 1
    t2, _ = eng.run_window([job], 5)   # recompute-resume
    job.generated.extend(t2[0])
    want = greedy_reference(cfg, params, [9, 8, 7], 10)
    assert job.generated == want
    assert job.generated[:5] == t1[0]


def test_two_slots_independent(setup):
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=128, max_output=64, eos_id=-1))
    j0 = Job(job_id=3, prompt="a", prompt_tokens=[1, 2, 3], arrival_time=0.0)
    j1 = Job(job_id=4, prompt="b", prompt_tokens=[4, 5, 6, 7, 8],
             arrival_time=0.0)
    toks, _ = eng.run_window([j0, j1], 8)
    assert toks[0] == greedy_reference(cfg, params, [1, 2, 3], 8)
    assert toks[1] == greedy_reference(cfg, params, [4, 5, 6, 7, 8], 8)


def test_eos_truncates_and_finishes(setup):
    cfg, params = setup
    # find the first greedy token and use it as the EOS id -> finishes at once
    first = greedy_reference(cfg, params, [11, 22, 33, 44], 1)[0]
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=1, max_len=128, max_output=64, eos_id=first))
    job = Job(job_id=5, prompt="x", prompt_tokens=[11, 22, 33, 44],
              arrival_time=0.0)
    toks, fin = eng.run_window([job], 10)
    assert fin[0] and toks[0] == [first]


def test_executor_capacity_guard(setup):
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(max_slots=1, max_len=128))
    ex = EngineExecutor({0: eng})
    jobs = [Job(job_id=i + 10, prompt="x", prompt_tokens=[1, 2],
                arrival_time=0.0) for i in range(2)]
    with pytest.raises(RuntimeError):
        ex.execute(0, jobs, 5, 0.0)


# =========================================================================== #
# Fast path: batched bucketed prefill + masked (compacted) decode
# =========================================================================== #


def _mk(i, toks):
    return Job(job_id=i, prompt=f"p{i}", prompt_tokens=list(toks),
               arrival_time=0.0)


def test_batched_prefill_matches_serial(setup):
    """One (batch, seq)-bucketed prefill dispatch == N batch-1 dispatches."""
    cfg, params = setup
    base = dict(max_slots=4, max_len=128, max_output=64, eos_id=-1)
    prompts = [[11, 22, 33, 44], [5, 6, 7], [9, 8, 7, 6, 5],
               [1, 2, 3, 4, 5, 6, 7]]
    eb = InferenceEngine(cfg, params, EngineConfig(batched_prefill=True,
                                                   **base))
    es = InferenceEngine(cfg, params, EngineConfig(
        batched_prefill=False, masked_decode=False, **base))
    tb, fb = eb.run_window([_mk(i, p) for i, p in enumerate(prompts)], 8)
    ts, fs = es.run_window([_mk(i, p) for i, p in enumerate(prompts)], 8)
    assert tb == ts and fb == fs
    assert eb.num_prefill_dispatches == 1
    assert es.num_prefill_dispatches == len(prompts)
    assert np.array_equal(np.asarray(eb.cache["len"]),
                          np.asarray(es.cache["len"]))


def test_prefill_compiles_once_per_bucket(setup):
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=4, max_len=128, max_output=64, eos_id=-1))
    eng.add_jobs([_mk(0, range(4)), _mk(1, range(6))])     # (2, 16)
    eng.add_jobs([_mk(2, range(20))])                      # (1, 32)
    assert eng.num_prefill_traces == 2
    eng.evict_job(2)
    eng.add_jobs([_mk(3, range(18))])                      # (1, 32) again
    assert eng.num_prefill_traces == 2, "same bucket retraced"
    assert eng.num_prefill_traces <= eng.prefill_shape_bound()
    with pytest.raises(ValueError):
        eng.add_jobs([_mk(9, range(300))])                 # > max_len


def test_add_job_on_full_engine_raises_before_dispatch(setup):
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=1, max_len=64, max_output=64, eos_id=-1))
    slot = eng.add_job(_mk(0, [1, 2, 3]))
    assert eng.add_job(_mk(0, [1, 2, 3])) == slot  # idempotent re-admit
    dispatches = eng.num_prefill_dispatches
    with pytest.raises(RuntimeError, match="free slots"):
        eng.add_job(_mk(1, [4, 5, 6]))
    assert eng.num_prefill_dispatches == dispatches  # no wasted prefill


def test_masked_decode_compacts_to_bucket(setup):
    """Decode dispatches are shaped by the *scheduled* batch bucket, not
    max_slots, and one compiled shape serves repeated windows."""
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=4, max_len=128, max_output=64, eos_id=-1))
    jobs = [_mk(0, [11, 22, 33]), _mk(1, [5, 6, 7])]
    eng.run_window(jobs, 4)
    assert (4, 2) in eng._window_cache and (4, 4) not in eng._window_cache
    eng.run_window(jobs, 4)
    assert eng.num_decode_dispatches == 2
    assert eng.num_decode_traces == 1


def test_unscheduled_slot_is_frozen_and_resumes_exactly(setup):
    """An occupied slot left out of the scheduled batch must be untouched
    by the dispatch (no stale-KV corruption) and continue bit-exactly."""
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=128, max_output=64, eos_id=-1))
    j0, j1 = _mk(0, [11, 22, 33, 44]), _mk(1, [5, 6, 7])
    t1, _ = eng.run_window([j0, j1], 6)
    j0.generated.extend(t1[0])
    j1.generated.extend(t1[1])
    s0 = eng.slot_of[0]
    len_before = int(np.asarray(eng.cache["len"])[s0])
    t2, _ = eng.run_window([j1], 5)       # j0 occupied but NOT scheduled
    j1.generated.extend(t2[0])
    assert int(np.asarray(eng.cache["len"])[s0]) == len_before
    t3, _ = eng.run_window([j0], 6)       # j0 continues from frozen cache
    j0.generated.extend(t3[0])
    assert j0.generated == greedy_reference(cfg, params, [11, 22, 33, 44], 12)
    assert j1.generated == greedy_reference(cfg, params, [5, 6, 7], 11)


def test_preempt_resume_with_slot_recycling(setup):
    """Evict + re-add recomputes from the preserved partial output even
    after ANOTHER job has decoded in the recycled slot (stale KV would
    corrupt the stream if resume didn't recompute)."""
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=1, max_len=128, max_output=64, eos_id=-1))
    victim = _mk(0, [9, 8, 7])
    t1, _ = eng.run_window([victim], 5)
    victim.generated.extend(t1[0])
    eng.evict_job(0)                       # preemption
    thief = _mk(1, [1, 2, 3, 4])
    t2, _ = eng.run_window([thief], 7)     # recycles the slot
    thief.generated.extend(t2[0])
    eng.evict_job(1)
    t3, _ = eng.run_window([victim], 5)    # recompute-resume
    victim.generated.extend(t3[0])
    assert victim.generated == greedy_reference(cfg, params, [9, 8, 7], 10)
    assert thief.generated == greedy_reference(cfg, params, [1, 2, 3, 4], 7)


def test_pallas_decode_matches_xla(setup):
    """attn_impl="pallas" routes decode through the flash-decode kernel
    against the slot cache; greedy tokens must match the XLA oracle."""
    cfg, params = setup
    outs = {}
    for impl in ("xla", "pallas"):
        eng = InferenceEngine(cfg, params, EngineConfig(
            max_slots=2, max_len=64, max_output=64, eos_id=-1,
            attn_impl=impl))
        outs[impl], _ = eng.run_window(
            [_mk(0, [11, 22, 33, 44]), _mk(1, [5, 6, 7])], 6)
    assert outs["xla"] == outs["pallas"]


def test_inplace_decode_window_matches_xla(setup):
    """One decode window on the pallas path, whose layer loop carries the
    stacked KV cache and writes each row in place, against the XLA path:
    an unscheduled slot, an empty slot and an EOS mid-window give the same
    tokens and lengths, and the frozen rows' cache bytes stay identical in
    every layer.  Only the kernel path counts as read in place."""
    from repro.models import transformer as T

    cfg, params = setup
    prompts = [[11, 22, 33, 44], [5, 6, 7], [9, 8, 7, 6, 5]]
    K = 6
    # the window's first token is greedy token 2 (token 1 comes from the
    # prefill logits): job 2 emits EOS at window step 1, job 0 never
    eos = greedy_reference(cfg, params, prompts[2], 3)[2]
    out = {}
    for impl in ("xla", "pallas"):
        eng = InferenceEngine(cfg, params, EngineConfig(
            max_slots=4, max_len=64, max_output=64, eos_id=eos,
            attn_impl=impl))
        jobs = [_mk(i, p) for i, p in enumerate(prompts)]
        eng.add_jobs(jobs)
        before = eng.cache
        alive = np.zeros((4,), bool)
        alive[[eng.slot_of[0], eng.slot_of[2]]] = True
        cache, toks = eng._decode_window(K, 4)(
            eng.params, before, jnp.asarray(eng.last_token),
            jnp.asarray(alive), jax.random.PRNGKey(1))
        grew = np.asarray(cache["len"]) - np.asarray(before["len"])
        assert grew[eng.slot_of[0]] == K
        assert grew[eng.slot_of[2]] == 2  # frozen after its EOS
        frozen = [eng.slot_of[1], 3]
        assert not grew[frozen].any()
        for new, old in zip(jax.tree_util.tree_leaves(cache["kv"]),
                            jax.tree_util.tree_leaves(before["kv"])):
            assert np.array_equal(np.asarray(new)[:, frozen],
                                  np.asarray(old)[:, frozen])
        out[impl] = np.asarray(toks), np.asarray(cache["len"])

        ex = EngineExecutor({0: eng})
        ex.execute(0, jobs[1:], K, 0.0)
        c = ex.counters()
        assert c["decode_dispatches"] == 1
        assert c["decode_kv_inplace"] == (1 if impl == "pallas" else 0)
    assert np.array_equal(out["xla"][0], out["pallas"][0])
    assert np.array_equal(out["xla"][1], out["pallas"][1])
    # the int8 and ring caches are read through XLA, a slice per layer
    for kv in ({"kv_dtype": "int8"}, {"sliding_window": 32}):
        assert not T.decode_reads_in_place(
            cfg, T.init_cache(cfg, 4, 64, **kv), "pallas")


# =========================================================================== #
# EngineExecutor: counters + live<->sim calibration
# =========================================================================== #


def test_executor_counters_and_window_log(setup):
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=64, max_output=64, eos_id=-1))
    ex = EngineExecutor({0: eng})
    jobs = [_mk(0, [11, 22, 33]), _mk(1, [5, 6, 7])]
    ex.execute(0, jobs, 4, 0.0)
    c = ex.counters()
    assert c["windows_executed"] == 1 and c["decode_dispatches"] == 1
    assert c["prefill_dispatches"] == 1 and c["prefill_traces"] >= 1
    assert ex.window_log[0]["batch"] == 2
    assert ex.window_log[0]["duration_s"] > 0


def test_calibrated_profile_recovers_latency_model(setup):
    """The live->sim fit inverts duration = o + K*d1*(1+slow*(b-1))."""
    cfg, params = setup
    eng = InferenceEngine(cfg, params, EngineConfig(max_slots=4, max_len=64))
    ex = EngineExecutor({0: eng})
    o, d1, slow = 0.004, 0.003, 0.1
    for b in (1, 2, 4):
        for w in (4, 8):
            dur = o + w * d1 * (1 + slow * (b - 1))
            for _ in range(3):  # first occurrence per shape is dropped
                ex.window_log.append({"node": 0, "batch": b, "window": w,
                                      "duration_s": dur, "tokens": b * w})
    prof = ex.calibrated_profile(name="fit-test")
    assert abs(prof.decode_ms_1 - d1 * 1000) / (d1 * 1000) < 0.05, prof
    assert abs(prof.batch_slowdown - slow) < 0.02, prof
    assert abs(ex.fit_overhead_s - o) < 5e-4
    assert prof.n_layers == cfg.n_layers


# =========================================================================== #
# Property tests: slot bookkeeping under interleaved add/evict/EOS churn
# =========================================================================== #

_PROP = {}


def _prop_engine():
    """One shared engine for the property suite — shapes compile once."""
    if not _PROP:
        cfg = get_config("qwen2-1.5b").reduced()
        params = init_params(jax.random.PRNGKey(0), cfg)
        _PROP["cfg"], _PROP["params"] = cfg, params
        _PROP["eng"] = InferenceEngine(cfg, params, EngineConfig(
            max_slots=4, max_len=64, max_output=8, eos_id=-1,
            respect_job_max=True))
        _PROP["greedy"] = {}
    return _PROP["eng"]


def _prop_greedy(prompt, n):
    key = tuple(prompt)
    have = _PROP["greedy"].get(key, [])
    if len(have) < n:
        have = greedy_reference(_PROP["cfg"], _PROP["params"], prompt, n)
        _PROP["greedy"][key] = have
    return have[:n]


def _check_bookkeeping(eng):
    occupied = [s for s, j in enumerate(eng.slot_job) if j is not None]
    assert eng.free_slots() == eng.cfg.max_slots - len(occupied)
    assert sorted(eng.slot_of.values()) == occupied
    for job_id, slot in eng.slot_of.items():
        assert eng.slot_job[slot] == job_id


@settings(max_examples=6, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["add", "evict", "run"]),
                          st.integers(0, 7)),
                min_size=4, max_size=12))
def test_slot_bookkeeping_survives_interleaving(ops):
    """free_slots/slot_of stay consistent and every job's emitted stream
    equals the greedy oracle prefix under interleaved add / evict
    (preempt) / run-to-EOS sequences (jobs cap at max_output=8, so EOS-like
    completion and slot recycling happen organically)."""
    eng = _prop_engine()
    # drain anything a previous example left behind
    for jid in list(eng.slot_of):
        eng.evict_job(jid)
    live, done, next_id = {}, [], [1000]
    prompts = [[11, 22, 33], [5, 6, 7, 8], [9, 8, 7], [1, 2, 3, 4, 5]]

    for op, v in ops:
        if op == "add" and eng.free_slots() > 0:
            job = Job(job_id=next_id[0], prompt="p",
                      prompt_tokens=prompts[v % len(prompts)],
                      arrival_time=0.0, true_output_len=4 + v % 5)
            next_id[0] += 1
            eng.add_jobs([job])
            live[job.job_id] = job
        elif op == "evict" and live:
            jid = sorted(live)[v % len(live)]
            eng.evict_job(jid)          # preemption: job keeps its output
        elif op == "run" and live:
            # preempted jobs resume only while slots remain (the frontend's
            # batch formation enforces the same bound via free_capacity)
            holding = [live[j] for j in sorted(live) if eng.has_job(j)]
            slotless = [live[j] for j in sorted(live)
                        if not eng.has_job(j)][: eng.free_slots()]
            batch = holding + slotless
            if not batch:
                continue
            toks, fins = eng.run_window(batch, 2)
            for job, t, fin in zip(batch, toks, fins):
                job.generated.extend(t)
                if fin:
                    eng.evict_job(job.job_id)
                    done.append(live.pop(job.job_id))
        _check_bookkeeping(eng)

    for job in list(live.values()) + done:
        if job.generated:
            want = _prop_greedy(list(job.prompt_tokens), len(job.generated))
            assert list(job.generated) == want, (
                f"job {job.job_id} diverged from the greedy oracle")
