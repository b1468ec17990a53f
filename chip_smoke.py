"""Prove that ELIS serves qwen2-1.5b at its published widths on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # only the paths that need four chips

One process drives every phase; no phase catches its own failure, so any
failed check ends the script with a traceback and a non-zero exit.  The
last line of stdout is one JSON object naming the device.

One chip:
  serve_xla, serve_pallas  ``repro.launch.serve.main`` at published widths
      (28 layers, bf16, seeded random weights) with ISRTF and the BGE
      predictor: 8 requests, 16 slots x 2048 tokens, 32 output tokens each.
      Every request must finish; the greedy-token agreement between the two
      attention paths is printed.
  engine   one-chip engines on the same weights: the pallas decode program
      holds the kernel (``tpu_custom_call``), and pallas prefill logits
      match the XLA engine's within LOGIT_TOL.
  kernels  each Pallas kernel against ``repro.kernels.ref`` at the engine's
      shapes (ssd_scan at mamba2-130m widths), within KERNEL_TOL.

Four chips (``--four-chips``):
  replicas  ``serve --mesh 4x1 --placement least_eta``: every pod serves
      requests; each pod's arrays sit on its own device, and its prefill
      logits and greedy tokens for a fixed prompt equal the one-chip
      engine's bit for bit.
  tp2       ``serve --mesh 2x2 --attn-impl pallas``: two TP=2 pods whose
      decode runs the kernel under ``shard_map``; their first-prefill logits
      match the one-chip engine within LOGIT_TOL.

The numbers printed before the last line (wall seconds, compiles, peak
device memory) are one-off smoke numbers, not benchmark results.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-1.5b"
SLOTS, MAX_LEN, MAX_OUTPUT, WINDOW = 16, 2048, 32, 8
SERVE_ARGS = ["--arch", ARCH, "--published-widths", "--policy", "isrtf",
              "--predictor", "bge", "--n", "8", "--slots", str(SLOTS),
              "--max-len", str(MAX_LEN), "--max-output", str(MAX_OUTPUT),
              "--window", str(WINDOW), "--seed", "0"]
#: a fixed prompt for the engine-level comparisons
PROMPT = [11 + (7 * i) % 400 for i in range(24)]

#: kernel vs reference, bf16 outputs: |got - want| <= atol + rtol * |want|
#: (one bf16 rounding of an O(1) output is 2^-8 ~ 4e-3)
KERNEL_TOL = 2e-2
#: logits of two engines over 28 bf16 layers:
#: max |a - b| <= LOGIT_TOL * max |b|
LOGIT_TOL = 5e-2


def require_tpu(devices) -> None:
    """Fail unless JAX runs on a TPU: no result may come from a CPU run."""
    platform = devices[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found platform={platform!r} "
            f"({len(devices)} device(s))")


class CompileLog:
    """Counts backend compiles (and their seconds) through JAX's
    monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def run_phase(name, fn, log):
    import jax

    n0, s0 = log.count, log.seconds
    t0 = time.perf_counter()
    fn()
    wall = time.perf_counter() - t0
    gc.collect()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    print(f"[smoke] phase={name} wall_s={wall:.3f} "
          f"compiles={log.count - n0} compile_s={log.seconds - s0:.3f} "
          f"peak_bytes_in_use={max(peaks)}", flush=True)


def serve(extra):
    """Run ``serve.main`` in this process; return its per-request records
    after checking that every request finished."""
    from repro.launch import serve as serve_mod

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = serve_mod.main(SERVE_ARGS + extra)
    # the engines' jitted closures hold them in reference cycles: free
    # their device memory before the next phase allocates its own
    gc.collect()
    recs = [json.loads(line) for line in out.getvalue().splitlines()
            if line.startswith("{")]
    unfinished = [r["request_id"] for r in recs if r["status"] != "finished"]
    if rc != 0 or unfinished or len(recs) != 8:
        raise RuntimeError(f"serve {extra}: rc={rc}, {len(recs)} records, "
                           f"unfinished={unfinished}")
    return {r["request_id"]: r for r in recs}


def model_and_params():
    import jax

    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config(ARCH)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def engine_cfg(attn_impl):
    from repro.engine import EngineConfig

    return EngineConfig(max_slots=SLOTS, max_len=MAX_LEN,
                        max_output=MAX_OUTPUT, eos_id=-1,
                        attn_impl=attn_impl)


def greedy_tokens(eng, n=2 * WINDOW):
    from repro.core import Job

    job = Job(job_id=0, prompt="smoke", prompt_tokens=list(PROMPT),
              arrival_time=0.0)
    while len(job.generated) < n:
        toks, _ = eng.run_window([job], WINDOW)
        job.generated.extend(toks[0])
    eng.evict_job(job.job_id)
    return job.generated[:n]


def check_logits(name, got, want):
    import numpy as np

    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise RuntimeError(f"{name}: non-finite logits")
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    print(f"[smoke] {name} logits max_rel_err={err:.6f} (tol {LOGIT_TOL})",
          flush=True)
    if err > LOGIT_TOL:
        raise RuntimeError(f"{name}: logits differ by {err} > {LOGIT_TOL}")


def require_kernel(lowered, name):
    if "tpu_custom_call" not in lowered.as_text():
        raise RuntimeError(f"{name}: the decode program holds no Pallas "
                           "kernel (tpu_custom_call)")


# --------------------------------------------------------------------------- #
# One chip
# --------------------------------------------------------------------------- #


def phase_serve(state):
    state["xla"] = serve(["--attn-impl", "xla"])


def phase_serve_pallas(state):
    pallas = serve(["--attn-impl", "pallas"])
    xla = state.pop("xla")
    same = total = exact = 0
    for rid, r in pallas.items():
        a, b = r["tokens"], xla[rid]["tokens"]
        same += sum(x == y for x, y in zip(a, b))
        total += max(len(a), len(b))
        exact += a == b
    print(f"[smoke] xla<->pallas greedy-token agreement {same}/{total} "
          f"= {same / total:.4f}; identical requests {exact}/{len(pallas)}",
          flush=True)


def phase_engine(state):
    from repro.engine import InferenceEngine

    cfg, params = model_and_params()
    xla = InferenceEngine(cfg, params, engine_cfg("xla"))
    pallas = InferenceEngine(cfg, params, engine_cfg("pallas"))
    check_logits("pallas-vs-xla prefill", pallas.prefill_logits(PROMPT),
                 xla.prefill_logits(PROMPT))
    toks = greedy_tokens(pallas)
    require_kernel(pallas.lower_decode_window(WINDOW, 1), "pallas engine")
    print(f"[smoke] pallas engine greedy tokens {toks}", flush=True)


def _close(name, got, want, tol=KERNEL_TOL):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise RuntimeError(f"{name}: shape {got.shape} vs {want.shape}, "
                           f"finite={np.isfinite(got).all()}")
    excess = np.abs(got - want) - (tol + tol * np.abs(want))
    print(f"[smoke] kernel {name} {got.shape} max_abs_err="
          f"{float(np.max(np.abs(got - want))):.6f} (tol {tol})", flush=True)
    if float(np.max(excess)) > 0:
        raise RuntimeError(f"{name}: outside tolerance by "
                           f"{float(np.max(excess))}")


def phase_kernels(state):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.models.layers import dequantize_kv, quantize_kv

    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    h, kh, d = 12, 2, 128  # qwen2-1.5b heads / kv heads / head dim
    highest = jax.default_matmul_precision("highest")

    # decode: the engine's full slot cache, slots at mixed depths
    q = jax.random.normal(ks[0], (SLOTS, 1, h, d), bf)
    k = jax.random.normal(ks[1], (SLOTS, MAX_LEN, kh, d), bf)
    v = jax.random.normal(ks[2], (SLOTS, MAX_LEN, kh, d), bf)
    kv_len = jax.random.randint(ks[3], (SLOTS,), 1, MAX_LEN + 1)
    got = ops.flash_decode(q, k, v, kv_len=kv_len, q_offset=kv_len - 1)
    with highest:
        want = ref.reference_decode_attention(q, k, v, kv_len=kv_len,
                                              q_offset=kv_len - 1)
    _close("flash_decode", got, want)

    kq, ksc = quantize_kv(k)
    vq, vsc = quantize_kv(v)
    got = ops.flash_decode_int8(q, kq, vq, ksc, vsc, kv_len=kv_len,
                                q_offset=kv_len - 1)
    with highest:
        want = ref.reference_decode_attention(
            q, dequantize_kv(kq, ksc, jnp.float32),
            dequantize_kv(vq, vsc, jnp.float32),
            kv_len=kv_len, q_offset=kv_len - 1)
    _close("flash_decode_int8", got, want)

    # prefill: a batched prompt bucket and one full-length prompt
    for b, s in ((8, 64), (1, MAX_LEN)):
        q = jax.random.normal(ks[4], (b, s, h, d), bf)
        k = jax.random.normal(ks[5], (b, s, kh, d), bf)
        v = jax.random.normal(ks[6], (b, s, kh, d), bf)
        got = ops.flash_attention(q, k, v, causal=True)
        with highest:
            want = ref.reference_attention(q, k, v, causal=True)
        _close(f"flash_attention[{b}x{s}]", got, want)

    # ssd_scan at mamba2-130m widths: 24 heads, head dim 64, state 128
    b, s, nh, p, n, chunk = 1, 1024, 24, 64, 128, 256
    kx, ka, kb, kc = jax.random.split(ks[7], 4)
    x = jax.random.normal(kx, (b, s, nh, p), bf)
    a = -0.1 * jnp.abs(jax.random.normal(ka, (b, s, nh), jnp.float32))
    bm = jax.random.normal(kb, (b, s, nh, n), bf)
    cm = jax.random.normal(kc, (b, s, nh, n), bf)
    y, fs = ops.ssd_scan(x, a, bm, cm, chunk=chunk)
    with highest:
        y_ref, fs_ref = ref.reference_ssd(
            x.astype(jnp.float32), a, bm.astype(jnp.float32),
            cm.astype(jnp.float32), chunk)
    # the scan's outputs grow with the chunk: compare relative to their scale
    scale = float(jnp.max(jnp.abs(y_ref)))
    _close("ssd_scan.y/scale", y / scale, y_ref / scale)
    scale = float(jnp.max(jnp.abs(fs_ref)))
    _close("ssd_scan.state/scale", fs / scale, fs_ref / scale)


ONE_CHIP = [("serve_xla", phase_serve), ("serve_pallas", phase_serve_pallas),
            ("engine", phase_engine), ("kernels", phase_kernels)]


# --------------------------------------------------------------------------- #
# Four chips
# --------------------------------------------------------------------------- #


def phase_replicas(state):
    import jax
    import numpy as np

    from repro.engine import InferenceEngine, make_tp_pods

    recs = serve(["--mesh", "4x1", "--placement", "least_eta",
                  "--rate", "50"])
    nodes = sorted({r["node"] for r in recs.values()})
    print(f"[smoke] replicas: requests per pod "
          f"{[sum(r['node'] == n for r in recs.values()) for n in range(4)]}",
          flush=True)
    if nodes != [0, 1, 2, 3]:
        raise RuntimeError(f"least_eta placed requests on pods {nodes} only")

    cfg, params = model_and_params()
    one_chip = InferenceEngine(cfg, params, engine_cfg("xla"))
    ref_logits = one_chip.prefill_logits(PROMPT)
    ref_toks = greedy_tokens(one_chip)
    del one_chip
    devices = jax.devices()
    pods = make_tp_pods(cfg, params, engine_cfg("xla"), n_pods=4, tp=1)
    for n, eng in pods.items():
        # random weights make greedy decoding repeat one token, so the
        # prefill logits are compared bit for bit as well
        if not np.array_equal(eng.prefill_logits(PROMPT), ref_logits):
            raise RuntimeError(f"pod {n} prefill logits differ from one "
                               "chip's")
        toks = greedy_tokens(eng)
        leaves = jax.tree_util.tree_leaves((eng.params, eng.cache))
        where = {dev for leaf in leaves for dev in leaf.devices()}
        if where != {devices[n]}:
            raise RuntimeError(f"pod {n} arrays sit on {where}, "
                               f"not on {devices[n]}")
        if toks != ref_toks:
            raise RuntimeError(f"pod {n} tokens {toks} != one-chip "
                               f"{ref_toks}")
    print(f"[smoke] replicas: 4 pods on 4 devices, prefill logits and "
          f"tokens identical to one chip: {ref_toks}", flush=True)


def phase_tp2(state):
    from repro.engine import InferenceEngine, make_tp_pods

    serve(["--mesh", "2x2", "--attn-impl", "pallas"])
    cfg, params = model_and_params()
    want = InferenceEngine(cfg, params, engine_cfg("xla")).prefill_logits(
        PROMPT)
    pods = make_tp_pods(cfg, params, engine_cfg("pallas"), n_pods=2, tp=2)
    for n, eng in pods.items():
        if eng.pallas_fallback:
            raise RuntimeError(f"TP pod {n} fell back: "
                               f"{eng.pallas_fallback_reason}")
        check_logits(f"tp2 pod {n} vs one chip", eng.prefill_logits(PROMPT),
                     want)
        print(f"[smoke] tp2 pod {n} greedy tokens {greedy_tokens(eng)}",
              flush=True)
        require_kernel(eng.lower_decode_window(WINDOW, 1), f"tp2 pod {n}")


FOUR_CHIPS = [("replicas", phase_replicas), ("tp2", phase_tp2)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths (replicas, TP=2)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    require_tpu(devices)
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        raise RuntimeError(f"needs {need} chips, JAX found {len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[smoke] compile cache {enable_compile_cache()}", flush=True)
    log = CompileLog()
    state = {}
    for name, fn in FOUR_CHIPS if args.four_chips else ONE_CHIP:
        run_phase(name, lambda: fn(state), log)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
