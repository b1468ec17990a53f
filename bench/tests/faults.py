"""Helpers of the fault tests: a whole run at a tiny size on the CPU with
the look for a chip skipped, and the faults a one-chip serving cell can
have: a decode step that returns its state unchanged, half of the batch
left out (its rows given the mean of the rest), and a token altered where
it is produced.  (No cell exchanges anything between chips.)"""
import time

import jax.numpy as jnp

from bench import harness, registry
from bench.tests import tiny


def quick(monkeypatch):
    """Cut set-up to what a test run can hold, and skip the chip."""
    monkeypatch.setattr(harness, "TRAIN_STEPS", 5)
    monkeypatch.setattr(harness, "TRAIN_REQUESTS", 40)
    v5e = registry.peaks("TPU v5 lite")
    monkeypatch.setattr(registry, "peaks", lambda kind: v5e)
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "off")
    # the predictor's pool never nears the whole schedule at this load
    warm = harness.warm_up
    monkeypatch.setattr(harness, "warm_up",
                        lambda sys_, max_pool: warm(sys_, 16))


def run(tmp_path, seed, trace=False):
    # busy enough that decode batches hold several jobs
    cell = tiny.cell("yi-6b-16l.chat-steady", check_requests=12,
                     drain_cap_s=5.0)
    cell.cfg["serving"].update(slots=4, knee_rps=16.0)
    return harness.run(cell, seed, 1.5, trace, time.perf_counter(),
                       tmp_path)


def state_unchanged(monkeypatch):
    from repro.models import transformer as T

    real = T.decode_step

    def step(params, cfg, tokens, cache, **kw):
        logits, _ = real(params, cfg, tokens, cache, **kw)
        return logits, cache

    monkeypatch.setattr(T, "decode_step", step)


def half_batch(monkeypatch):
    from repro.models import transformer as T

    real = T.decode_step

    def step(params, cfg, tokens, cache, **kw):
        logits, cache = real(params, cfg, tokens, cache, **kw)
        b = logits.shape[0]
        if b >= 2:
            mean = jnp.mean(logits[: b // 2], axis=0, keepdims=True)
            logits = logits.at[b // 2:].set(
                jnp.broadcast_to(mean, logits[b // 2:].shape))
        return logits, cache

    monkeypatch.setattr(T, "decode_step", step)


def token_altered(monkeypatch):
    from repro.engine import engine as E

    real = E.sample

    def sample(logits, key, cfg, **kw):
        return (real(logits, key, cfg, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(E, "sample", sample)


