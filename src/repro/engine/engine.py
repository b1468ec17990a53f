"""JAX inference engine — the vLLM analogue the ELIS backend workers drive.

TPU-idiomatic design (see DESIGN.md §3): instead of paged KV blocks, a
fixed-capacity **slot-based** cache — every decode slot owns a contiguous
KV/state region of a statically-shaped batched cache, and slots advance
independently (per-slot ``len`` vector).  Slot recycling replaces page
allocation; preemption = slot eviction + recompute-on-resume.

The two features the paper adds to vLLM are first-class here:
  * **iteration-wise execution** — ``run_window`` executes exactly K tokens
    (or to EOS) for the scheduled batch and returns partial outputs;
  * **configurable priorities** — the scheduler decides which jobs hold
    slots each window; ``evict``/``add`` implement priority preemption.

Fast path (DESIGN.md §3.2–§3.4):
  * **batched bucketed prefill** — ``add_jobs`` admits every newly scheduled
    job in ONE padded ``(batch_bucket, seq_bucket)`` prefill dispatch per
    window instead of N batch-1 calls; the shape-bucket ladder is the same
    one ``BGEPredictor`` uses (``repro.data.dataset``), so the jitted
    prefill compiles once per bucket no matter how admissions arrive.
    Attention families right-pad prompts to the bucket (causality + the
    kv_len mask make pads harmless); SSM/hybrid families keep exact-length
    batch-1 prefill because recurrent state would absorb pad positions.
  * **masked decode windows** — each decode dispatch carries a per-slot
    ``active`` mask (occupied ∧ not-EOS).  When occupancy is below capacity
    the engine *compacts*: it gathers the scheduled slots into a
    ``batch_bucket``-sized sub-cache, decodes only those rows, and scatters
    back —
    empty slots stop burning FLOPs.  Within the window, a slot that emits
    EOS is *frozen* for the remaining ``lax.scan`` steps: no KV/state
    write, no ``len`` advance, PAD emissions (see ``T.decode_step``).
  * **Pallas decode attention** — ``attn_impl="pallas"`` routes
    ``T.decode_step`` through :mod:`repro.kernels.decode_attention` with
    the per-slot ``len`` vector as kv lengths; ``"xla"`` stays the
    reference path (numerics-equivalence is CI-guarded).  Under a TP mesh
    the kernel runs ``shard_map``-ped over the "model" axis when the head
    layout supports it (DESIGN.md §11, docs/kernels.md); unsupported
    layouts, and prefill under a mesh, fall back loudly, once, with the
    reason.
  * **compile/dispatch counters** — ``num_prefill_traces`` /
    ``num_prefill_dispatches`` / ``num_decode_traces`` /
    ``num_decode_dispatches`` mirror ``BGEPredictor``'s recompile-storm
    hooks, ``num_decode_kv_inplace`` counts the decode dispatches whose
    program read the stacked KV cache in place (the Pallas kernel over a
    dense cache, ``T.decode_reads_in_place``), and ``num_host_syncs``
    counts device-to-host reads (each one an
    ``elis.engine.sync`` span, :mod:`repro.spans`); the jitted programs are
    named ``prefill``, ``prefill_chunk`` and ``decode_window``, so a device
    trace names them ``jit_prefill``, ``jit_prefill_chunk`` and
    ``jit_decode_window``; ``EngineExecutor.counters()`` aggregates them and
    ``EngineExecutor.calibrated_profile()`` fits the measured window
    durations back onto the simulator's latency model (live↔sim
    calibration).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.frontend import Backend, ExecResult
from repro.core.job import Job
from repro.data.dataset import batch_bucket, n_shape_buckets, seq_bucket
from repro.data.tokenizer import EOS_ID, PAD_ID
from repro.engine.sampler import SamplerConfig, sample
from repro.models import transformer as T
from repro.spans import span

#: recurrent-state families prefill at exact length (pad positions would be
#: absorbed into the state), so they keep serial batch-1 admission
EXACT_PREFILL_FAMILIES = ("ssm", "hybrid")


@dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 4
    max_len: int = 512
    max_output: int = 1024
    eos_id: int = EOS_ID
    #: smallest prefill sequence bucket; padded lengths follow the
    #: power-of-two ``repro.data.seq_bucket`` ladder up to ``max_len``
    prefill_bucket: int = 16
    sampler: SamplerConfig = field(default_factory=SamplerConfig)
    #: decode attention implementation: "xla" (einsum reference) or
    #: "pallas" (flash-decode kernel over the slot cache)
    attn_impl: str = "xla"
    #: admit all newly scheduled jobs in one padded (batch, seq)-bucketed
    #: prefill dispatch (False = one batch-1 dispatch per job, the
    #: pre-fast-path baseline kept for benchmarking)
    batched_prefill: bool = True
    #: compact decode dispatches to the batch bucket of the *scheduled*
    #: slots and freeze unscheduled/EOS slots (False = always decode the
    #: full ``max_slots`` batch, the pre-fast-path baseline)
    masked_decode: bool = True
    #: honour each request's own token budget (job.true_output_len acts as
    #: the request's ``max_tokens``, like vLLM's per-request cap)
    respect_job_max: bool = False


# --------------------------------------------------------------------------- #
# Slot-cache gather/scatter
# --------------------------------------------------------------------------- #


def _batch_axis(path, ndim: int) -> int:
    """Slot (batch) axis of a cache leaf.

    Convention (see T.init_cache): 1-D leaves are the per-slot ``len``
    vector; stacked KV/state leaves carry a leading layer/site axis with
    batch at axis 1 — except the hybrid family's ``groups_ssm``, whose
    states are stacked (n_groups, inner, batch, ...).
    """
    if ndim == 1:
        return 0
    top = getattr(path[0], "key", None)
    return 2 if top == "groups_ssm" else 1


def _gather_slots(cache, idx: jnp.ndarray):
    """Gather slot rows ``idx`` of the cache pytree into a sub-cache."""

    def take(path, leaf):
        return jnp.take(leaf, idx, axis=_batch_axis(path, leaf.ndim))

    return jax.tree_util.tree_map_with_path(take, cache)


def _scatter_slots(big, small, slots: Sequence[int], n: int):
    """Write rows ``0..n-1`` of the batched ``small`` cache pytree into the
    given ``slots`` of ``big`` (rows beyond ``n`` are bucket padding)."""
    sl = jnp.asarray(list(slots)[:n], jnp.int32)

    def put(path, b, s):
        ax = _batch_axis(path, b.ndim)
        if ax == 0:
            return b.at[sl].set(s[:n])
        if ax == 1:
            return b.at[:, sl].set(s[:, :n])
        return b.at[:, :, sl].set(s[:, :, :n])

    return jax.tree_util.tree_map_with_path(put, big, small)


def make_decode_window(model_cfg, cfg: EngineConfig, window: int,
                       mesh=None):
    """The decode program of one window: ``window`` steps of
    ``T.decode_step`` over a slot (sub-)cache, sampling each next token.
    ``decode_window(params, cache, last_tokens (B, 1), alive (B,), rng)
    -> (cache, tokens (B, window))``; the engine jits it per (window,
    batch), and it lowers from shapes alone."""

    def decode_window(params, cache, last_tokens, alive, rng):
        def step(carry, _):
            cache, toks, alive, rng = carry
            logits, cache = T.decode_step(params, model_cfg, toks, cache,
                                          attn_impl=cfg.attn_impl,
                                          active=alive, mesh=mesh)
            rng, sub = jax.random.split(rng)
            nxt = sample(logits[:, -1, :], sub, cfg.sampler,
                         active=alive, pad_token=PAD_ID)[:, None]
            # EOS freezes the slot for the rest of the scan: no
            # KV/state write, no len advance, PAD emissions
            alive = alive & (nxt[:, 0] != cfg.eos_id)
            return (cache, nxt, alive, rng), nxt[:, 0]

        (cache, _, _, _), toks = jax.lax.scan(
            step, (cache, last_tokens, alive, rng), None, length=window)
        return cache, jnp.swapaxes(toks, 0, 1)

    return decode_window


class InferenceEngine:
    """One backend worker's execution engine (one model, N slots).

    With ``mesh`` (a single-axis ``("model",)`` jax Mesh — one TP pod),
    parameters and the slot cache are sharded via ``repro.launch.partition``
    (heads/ffn/vocab on the "model" axis, slots replicated) and every
    prefill/decode dispatch is jitted with ``NamedSharding``-annotated
    inputs/outputs, so XLA inserts the tensor-parallel collectives.

    ``attn_impl="pallas"`` under a mesh runs the **mesh-aware** flash-decode
    kernel (``shard_map`` over "model", each shard attending its local KV
    heads — DESIGN.md §11, docs/kernels.md) whenever
    ``launch.partition.pallas_decode_support`` reports the layout supported;
    otherwise the engine warns **once**, with the reason, and falls back to
    the XLA decode path (``pallas_fallback`` / ``pallas_fallback_reason``).
    Prefill-side kernels stay single-device, so under a mesh prefill always
    uses the XLA path (identical numerics), with a one-time warning at the
    first prefill.

    With ``device`` (and no mesh) the engine is a one-chip replica pinned
    to that device: parameters, slot cache and every dispatch are
    committed there, so several replicas in one process never share a
    chip."""

    def __init__(self, model_cfg, params, cfg: Optional[EngineConfig] = None,
                 mesh=None, device=None):
        if cfg is None:
            cfg = EngineConfig()
        self.pallas_fallback = False
        #: why pallas fell back (None when it didn't): a reason string from
        #: ``launch.partition.pallas_decode_support``, category-prefixed
        #: ("mesh:" / "family:" / "layout:")
        self.pallas_fallback_reason: Optional[str] = None
        self.mesh = mesh
        self._warned: set = set()
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"engine mesh needs a 'model' axis, got {mesh.axis_names}")
            if cfg.attn_impl == "pallas":
                from repro.launch.partition import pallas_decode_support
                reason = pallas_decode_support(model_cfg, mesh)
                if reason is not None:
                    # the loud-fallback rule: never silently serve different
                    # numerics — but only for layouts the shard_map'd kernel
                    # genuinely cannot cover (DESIGN.md §11)
                    self._warn_once(
                        "pallas_fallback",
                        "attn_impl='pallas' cannot shard for this "
                        f"(config, mesh) — {reason}; falling back to the "
                        "XLA decode-attention path")
                    cfg = dataclasses.replace(cfg, attn_impl="xla")
                    self.pallas_fallback = True
                    self.pallas_fallback_reason = reason
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.cache = T.init_cache(model_cfg, cfg.max_slots, cfg.max_len)
        if mesh is not None:
            from repro.launch.partition import engine_shardings
            self._param_sh, self._cache_sh, self._repl = engine_shardings(
                mesh, model_cfg, params, self.cache)
        elif device is not None:
            one = jax.sharding.SingleDeviceSharding(device)
            self._param_sh = self._cache_sh = self._repl = one
        else:
            self._param_sh = self._cache_sh = self._repl = None
        if self._param_sh is None:
            self.params = params
        else:
            # one host copy of params serves any number of pods: each engine
            # device_puts onto its own (disjoint) devices
            self.params = jax.device_put(params, self._param_sh)
            self.cache = jax.device_put(self.cache, self._cache_sh)
        self.slot_job: List[Optional[int]] = [None] * cfg.max_slots
        self.slot_of: Dict[int, int] = {}
        self.last_token = np.full((cfg.max_slots, 1), PAD_ID, np.int32)
        self._key = jax.random.PRNGKey(0)

        #: compile/dispatch introspection (mirrors BGEPredictor's hooks):
        #: traces increment via a Python side effect that runs only while
        #: JAX traces a new input shape, so they count compiled shape
        #: buckets, not calls
        self.num_prefill_dispatches = 0
        self.num_decode_dispatches = 0
        #: decode dispatches whose program read the KV cache in place
        #: (``T.decode_reads_in_place``): no layer copied out and back
        self.num_decode_kv_inplace = 0
        #: device-to-host reads (``_sync``)
        self.num_host_syncs = 0
        self._prefill_traces = 0
        self._decode_traces = 0

        mc = model_cfg
        #: the prefill kernels are single-device: under a mesh prefill
        #: attends through XLA (warned once, at the first prefill)
        self._prefill_impl = "xla" if mesh is not None else cfg.attn_impl

        def prefill(params, tokens, cache1, last_index):
            self._prefill_traces += 1  # side effect: once per shape bucket
            batch = {"tokens": tokens}
            return T.prefill(params, mc, batch, cache1,
                             attn_impl=self._prefill_impl,
                             last_index=last_index)

        # params arrive TP-sharded (or committed to the replica's device),
        # the batched sub-cache replicates slots but shards heads/state, and
        # XLA inserts the all-reduces (wo / w_down partial sums)
        self._prefill = self._jit(
            prefill, (self._param_sh, self._repl, self._cache_sh,
                          self._repl), (self._repl, self._cache_sh))
        self._window_cache: Dict[Tuple[int, int], object] = {}
        #: first generated token (sampled from prefill logits), pending emission
        self._pending_first: Dict[int, int] = {}

        # ---- chunked prefill state (run_window(prefill_chunk=...)) ----
        #: jitted chunk dispatches, one per padded chunk length
        self._chunk_cache: Dict[int, object] = {}
        #: job_id -> tokens already span-written into its slot's cache
        self._prefill_cursor: Dict[int, int] = {}
        #: job_id -> total tokens to prefill (prompt, or resume context)
        self._chunk_target: Dict[int, int] = {}
        #: job_id -> the full token stream being chunk-prefilled
        self._chunk_tokens: Dict[int, List[int]] = {}
        #: job_id -> True when the chunked prefill re-establishes a resumed
        #: job's context (counts toward ``resume_context_tokens``)
        self._chunk_resumed: Dict[int, bool] = {}
        self.num_chunk_dispatches = 0
        self._chunk_traces = 0

        # ---- KV offload tier (offload_job/restore_job) ----
        #: job_id -> host-memory copy of the slot cache + decode bookkeeping
        self._host_stash: Dict[int, Dict] = {}
        #: watermark (stashed context tokens) bounding the host swap pool;
        #: None = unbounded.  ``EngineExecutor`` threads
        #: ``PreemptionConfig.swap_pool_tokens`` here; over-watermark
        #: swap-outs evict the COLDEST stashed victims to the
        #: recompute-fallback path (loud, once per engine)
        self.swap_pool_tokens: Optional[int] = None
        #: context tokens currently held in the host stash
        self.stash_tokens = 0
        #: stashes evicted by the watermark (victims fell back to recompute)
        self.n_stash_evictions = 0
        self.stash_evicted_tokens = 0

        #: tokens of context re-established by resume prefills (full or
        #: chunked), INCLUDING the +1 seed token whose KV is written by the
        #: first decode step — the live counterpart of the simulator's
        #: recompute charge (``SimExecutor.recompute_prefill_tokens``)
        self.resume_context_tokens = 0

    # ------------------------------------------------------------------ #
    def _warn_once(self, key: str, msg: str) -> None:
        """Emit a ``UserWarning`` at most ONCE per engine per ``key`` — the
        shared guard behind every loud-fallback site (pallas-under-mesh,
        unsupported chunked prefill).  Per-dispatch repetition would bury
        the reason; the message always carries it."""
        if key in self._warned:
            return
        self._warned.add(key)
        warnings.warn(msg, UserWarning, stacklevel=3)

    # ------------------------------------------------------------------ #
    def _sync(self, x):
        """Read ``x`` (an array or pytree) back to the host: the one door
        for every device-to-host read of the engine, counted in
        ``num_host_syncs``."""
        with span("elis.engine.sync"):
            self.num_host_syncs += 1
            return jax.device_get(x)

    def _jit(self, fn, in_shardings, out_shardings):
        """``jax.jit`` annotated with the engine's placement (a TP mesh or
        one replica device); plain ``jax.jit`` for an unplaced engine."""
        if self._param_sh is None:
            return jax.jit(fn)
        return jax.jit(fn, in_shardings=in_shardings,
                       out_shardings=out_shardings)

    def _canon_cache(self, cache):
        """Pin a cache pytree to the engine's canonical shardings.

        The slot gather/scatter and the host-side ``len`` updates run
        eagerly between jitted dispatches, and their outputs inherit
        whatever layout GSPMD propagated (or the default device); an
        explicit ``device_put`` keeps the persistent cache (and gathered
        sub-caches) exactly on the contract the annotated jits expect.
        No-op for an unplaced engine and free when the sharding already
        matches."""
        if self._cache_sh is None:
            return cache
        return jax.device_put(cache, self._cache_sh)

    # ------------------------------------------------------------------ #
    @property
    def num_prefill_traces(self) -> int:
        return self._prefill_traces

    @property
    def num_decode_traces(self) -> int:
        return self._decode_traces

    def prefill_shape_bound(self) -> int:
        """Upper bound on distinct prefill shapes the bucketing can emit
        (attention families; exact-length families are unbounded by
        design).  The CI smoke guard asserts ``num_prefill_traces`` stays
        under this no matter how admissions arrive."""
        return n_shape_buckets(self.cfg.max_slots, self.cfg.max_len,
                               self.cfg.prefill_bucket)

    def decode_batch_buckets(self) -> int:
        """Distinct decode batch sizes compaction can dispatch."""
        return len({min(batch_bucket(n), self.cfg.max_slots)
                    for n in range(1, self.cfg.max_slots + 1)})

    def prefill_logits(self, tokens: Sequence[int]) -> np.ndarray:
        """Last-position logits (V,) of one prompt through this engine's
        prefill program, taking no slot — a probe for comparing the
        numerics of two engines (e.g. a TP pod against one chip)."""
        sl = seq_bucket(len(tokens), self.cfg.max_len,
                        min_bucket=self.cfg.prefill_bucket)
        toks = np.full((1, sl), PAD_ID, np.int32)
        toks[0, :len(tokens)] = tokens
        logits, _ = self._prefill(
            self.params, jnp.asarray(toks),
            T.init_cache(self.model_cfg, 1, self.cfg.max_len),
            jnp.asarray([len(tokens) - 1], jnp.int32))
        return self._sync(logits[0, -1]).astype(np.float32)

    def lower_decode_window(self, window: int, batch: int):
        """Lower, without running, the decode-window program this engine
        dispatches for ``batch`` slots (``jax.stages.Lowered``) — its text
        shows which attention path the program holds."""
        sub = jax.eval_shape(
            lambda c: _gather_slots(c, jnp.zeros((batch,), jnp.int32)),
            self.cache)
        return self._decode_window(window, batch).lower(
            self.params, sub, jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            jax.ShapeDtypeStruct((batch,), jnp.bool_),
            jax.ShapeDtypeStruct(self._key.shape, self._key.dtype))

    # ------------------------------------------------------------------ #
    # Chunked prefill
    # ------------------------------------------------------------------ #

    @property
    def num_chunk_traces(self) -> int:
        return self._chunk_traces

    def chunk_supported(self) -> bool:
        """Chunked prefill needs a position-addressable dense KV cache:
        attention families only (recurrent state absorbs pads), no ring/SWA
        buffer (span writes are position-destructive there), no int8 KV
        (the chunk would attend a dequantized prefix while one-shot prefill
        attends the fresh unquantized K/V)."""
        if self.model_cfg.family not in T.CHUNKABLE_FAMILIES:
            return False
        kvc = self.cache.get("kv")
        return kvc is not None and not kvc.ring and not kvc.quantized

    def _chunk_fn(self, padded_len: int):
        """jit per padded chunk length (start/valid stay traced, so the
        whole prefill ladder reuses these few shapes)."""
        if padded_len not in self._chunk_cache:
            mc, ec = self.model_cfg, self.cfg

            def prefill_chunk(params, tokens, cache1, start, valid):
                self._chunk_traces += 1  # side effect: once per shape
                return T.prefill_chunk(params, mc, {"tokens": tokens}, cache1,
                                       attn_impl=ec.attn_impl,
                                       start=start, valid_len=valid)

            self._chunk_cache[padded_len] = self._jit(
                prefill_chunk, (self._param_sh, self._repl, self._cache_sh,
                                self._repl, self._repl),
                (self._repl, self._cache_sh))
        return self._chunk_cache[padded_len]

    def _alloc_slots(self, jobs: Sequence[Job]) -> None:
        """Claim a slot for each job WITHOUT prefilling (chunked
        admission): the slots' ``len`` is zeroed and each prompt is
        span-written chunk by chunk across subsequent windows (stale K/V
        from a previous occupant is dead weight behind the kv_len mask,
        exactly as after a one-shot scatter)."""
        free = [s for s, owner in enumerate(self.slot_job) if owner is None]
        if len(jobs) > len(free):
            raise RuntimeError("no free slot to allocate")
        streams = [self._resume_tokens(job) for job in jobs]
        for toks in streams:
            if len(toks) > self.cfg.max_len:
                raise ValueError(
                    f"prompt of {len(toks)} tokens exceeds max_len="
                    f"{self.cfg.max_len}")
        lens = self._sync(self.cache["len"]).copy()
        for job, slot, toks in zip(jobs, free, streams):
            self.slot_job[slot] = job.job_id
            self.slot_of[job.job_id] = slot
            self.last_token[slot, 0] = PAD_ID
            self._prefill_cursor[job.job_id] = 0
            self._chunk_target[job.job_id] = len(toks)
            self._chunk_tokens[job.job_id] = toks
            self._chunk_resumed[job.job_id] = bool(job.generated)
            lens[slot] = 0
        self.cache = self._canon_cache(
            {**self.cache, "len": jnp.asarray(lens)})

    def prefill_incomplete(self, job_id: int) -> bool:
        """True while a chunk-admitted job still has prompt tokens to
        ingest — such a job is excluded from decode dispatches."""
        cur = self._prefill_cursor.get(job_id)
        return cur is not None and cur < self._chunk_target[job_id]

    def _run_chunk(self, job: Job, chunk: int) -> None:
        """Ingest the next (at most) ``chunk`` prompt tokens of ``job`` in
        one batch-1 dispatch against its slot's partially-filled cache."""
        jid = job.job_id
        toks_all = self._chunk_tokens[jid]
        cur = self._prefill_cursor[jid]
        target = self._chunk_target[jid]
        n = min(chunk, target - cur)
        padded = seq_bucket(n, self.cfg.max_len,
                            min_bucket=self.cfg.prefill_bucket)
        toks = np.full((1, padded), PAD_ID, np.int32)
        toks[0, :n] = toks_all[cur:cur + n]
        slot = self.slot_of[jid]
        sub = self._canon_cache(
            _gather_slots(self.cache, jnp.asarray([slot], jnp.int32)))
        self.num_chunk_dispatches += 1
        logits, sub = self._chunk_fn(padded)(
            self.params, jnp.asarray(toks), sub,
            jnp.asarray([cur], jnp.int32), jnp.asarray([n], jnp.int32))
        self.cache = self._canon_cache(
            _scatter_slots(self.cache, sub, [slot], 1))
        self._prefill_cursor[jid] = cur + n
        if self._chunk_resumed[jid]:
            self.resume_context_tokens += n
        if cur + n >= target:
            # prefill complete: seed decode exactly like one-shot admission
            if job.generated:
                self.last_token[slot, 0] = job.generated[-1]
                self.resume_context_tokens += 1  # the seed token's KV write
            else:
                first = int(np.argmax(self._sync(logits)[0, -1]))
                self._pending_first[jid] = first
                self.last_token[slot, 0] = first

    # ------------------------------------------------------------------ #
    # KV offload tier
    # ------------------------------------------------------------------ #

    def offload_job(self, job_id: int) -> bool:
        """Evict a job's slot but keep its KV/state in HOST memory — resume
        swaps it back in instead of paying recompute.  ``jax.device_get``
        pulls every shard to host under a mesh; the stash also carries the
        decode bookkeeping (last token, pending first emission, chunk
        cursor) so a restored job continues bit-exactly.

        With ``swap_pool_tokens`` set, the host stash is bounded: an
        over-watermark swap-out evicts the COLDEST stashed victims (oldest
        swap-outs, insertion order) to the recompute-fallback path; if the
        fresh stash alone exceeds the pool it is refused (returns False, the
        caller falls back to plain eviction + recompute)."""
        slot = self.slot_of.get(job_id)
        if slot is None:
            return False
        with span("elis.engine.offload"):
            ctx = int(self._sync(self.cache["len"])[slot])
            sub = _gather_slots(self.cache, jnp.asarray([slot], jnp.int32))
            self._host_stash[job_id] = {
                "cache": self._sync(sub),
                "last": int(self.last_token[slot, 0]),
                "pending": self._pending_first.get(job_id),
                "cursor": self._prefill_cursor.get(job_id),
                "target": self._chunk_target.get(job_id),
                "tokens": self._chunk_tokens.get(job_id),
                "resumed": self._chunk_resumed.get(job_id),
                "ctx": ctx,
            }
        self.stash_tokens += ctx
        if self.swap_pool_tokens is not None:
            # evict coldest-first until under the watermark; the fresh
            # stash (newest) is only dropped when it alone exceeds the pool
            while (self.stash_tokens > self.swap_pool_tokens
                   and len(self._host_stash) > 1):
                self._evict_coldest_stash()
            if self.stash_tokens > self.swap_pool_tokens:
                self._evict_coldest_stash()  # the fresh stash itself
        self.evict_job(job_id)
        return job_id in self._host_stash

    def _evict_coldest_stash(self) -> None:
        """Watermark eviction: drop the oldest stash (coldest victim) —
        that job resumes through the recompute-fallback path."""
        victim, st = next(iter(self._host_stash.items()))
        del self._host_stash[victim]
        ctx = st.get("ctx", 0)
        self.stash_tokens -= ctx
        self.n_stash_evictions += 1
        self.stash_evicted_tokens += ctx
        self._warn_once(
            "swap_pool_evict",
            f"host KV swap pool exceeded its {self.swap_pool_tokens}-token "
            f"watermark (PreemptionConfig.swap_pool_tokens); evicting the "
            f"coldest stashed victims to recompute-fallback — raise the "
            f"watermark or reduce preemption pressure if swap-ins were "
            f"expected to stay warm")

    def restore_job(self, job: Job) -> int:
        """Swap a host-stashed job back into a free slot, bit-exactly."""
        st = self._host_stash.pop(job.job_id)
        self.stash_tokens -= st.get("ctx", 0)
        free = [s for s, owner in enumerate(self.slot_job) if owner is None]
        if not free:
            raise RuntimeError("no free slot to restore into")
        slot = free[0]
        sub = self._canon_cache(jax.device_put(st["cache"]))
        self.cache = self._canon_cache(
            _scatter_slots(self.cache, sub, [slot], 1))
        self.slot_job[slot] = job.job_id
        self.slot_of[job.job_id] = slot
        self.last_token[slot, 0] = st["last"]
        if st["pending"] is not None:
            self._pending_first[job.job_id] = st["pending"]
        if st["cursor"] is not None:
            self._prefill_cursor[job.job_id] = st["cursor"]
            self._chunk_target[job.job_id] = st["target"]
            self._chunk_tokens[job.job_id] = st["tokens"]
            self._chunk_resumed[job.job_id] = st["resumed"]
        return slot

    def has_stash(self, job_id: int) -> bool:
        return job_id in self._host_stash

    def drop_stash(self, job_id: int) -> None:
        """Release a job's host-memory KV copy (terminal states, or a
        migration that abandons the cache)."""
        st = self._host_stash.pop(job_id, None)
        if st is not None:
            self.stash_tokens -= st.get("ctx", 0)

    # ------------------------------------------------------------------ #
    def _decode_window(self, window: int, batch: int):
        """jit per (window length, compacted batch size) — both static."""
        key2 = (window, batch)
        if key2 not in self._window_cache:
            program = make_decode_window(self.model_cfg, self.cfg, window,
                                         self.mesh)

            def decode_window(params, cache, last_tokens, alive, rng):
                self._decode_traces += 1  # side effect: once per shape
                return program(params, cache, last_tokens, alive, rng)

            self._window_cache[key2] = self._jit(
                decode_window, (self._param_sh, self._cache_sh, self._repl,
                                self._repl, self._repl),
                (self._cache_sh, self._repl))
        return self._window_cache[key2]

    # ------------------------------------------------------------------ #
    def free_slots(self) -> int:
        return self.slot_job.count(None)

    def has_job(self, job_id: int) -> bool:
        return job_id in self.slot_of

    def _resume_tokens(self, job: Job) -> List[int]:
        """Token stream to prefill for a job.

        Fresh job: the prompt; *the first output token is sampled from the
        prefill logits* (emitted by the next ``run_window``).
        Resumed job (preempted earlier): recompute KV for
        ``prompt + generated[:-1]`` and seed decode with the last already-
        emitted token — nothing is double-emitted.
        """
        if job.generated:
            return list(job.prompt_tokens) + list(job.generated)[:-1]
        return list(job.prompt_tokens)

    def add_job(self, job: Job) -> int:
        """Prefill one job into a free slot (batch-1 dispatch).  A job
        already holding a slot keeps it (no double admission)."""
        return self.add_jobs([job])[0]

    def add_jobs(self, jobs: Sequence[Job]) -> List[int]:
        """Admit every job not yet holding a slot.

        Attention families: ONE padded ``(batch_bucket, seq_bucket)``
        prefill dispatch for the whole group.  SSM/hybrid (or
        ``batched_prefill=False``): serial batch-1 admissions.
        Returns each job's slot, aligned with ``jobs`` (already-admitted
        jobs report the slot they hold).
        """
        todo = [j for j in jobs if not self.has_job(j.job_id)]
        if todo:
            if len(todo) > self.free_slots():
                # all-or-nothing: fail before any partial serial admission
                raise RuntimeError(
                    f"admitting {len(todo)} jobs needs {len(todo)} free "
                    f"slots, engine has {self.free_slots()}")
            serial = (not self.cfg.batched_prefill
                      or self.model_cfg.family in EXACT_PREFILL_FAMILIES)
            with span("elis.engine.admit", jobs=len(todo)):
                if serial:
                    for j in todo:
                        self._admit([j])
                else:
                    self._admit(todo)
        return [self.slot_of[j.job_id] for j in jobs]

    def _admit(self, jobs: Sequence[Job]) -> List[int]:
        """One prefill dispatch admitting ``jobs``."""
        if len(jobs) > self.free_slots():
            # check BEFORE the dispatch: a full engine must fail loudly,
            # not pay a prefill and then mis-assign slots
            raise RuntimeError(
                f"admitting {len(jobs)} jobs needs {len(jobs)} free slots, "
                f"engine has {self.free_slots()}")
        exact = self.model_cfg.family in EXACT_PREFILL_FAMILIES
        token_lists = [self._resume_tokens(j) for j in jobs]
        true_lens = [len(t) for t in token_lists]
        longest = max(true_lens)
        if longest > self.cfg.max_len:
            raise ValueError(
                f"prompt of {longest} tokens exceeds max_len="
                f"{self.cfg.max_len}")
        if exact:
            # recurrent state must stay clean: exact length, batch 1
            assert len(jobs) == 1, "exact-length families admit serially"
            bb, sl = 1, true_lens[0]
        else:
            bb = batch_bucket(len(jobs))
            sl = seq_bucket(longest, self.cfg.max_len,
                            min_bucket=self.cfg.prefill_bucket)
        toks = np.full((bb, sl), PAD_ID, np.int32)
        last_index = np.zeros((bb,), np.int32)
        for i, t in enumerate(token_lists):
            toks[i, : len(t)] = t
            last_index[i] = len(t) - 1
        with span("elis.engine.alloc"):
            cacheN = T.init_cache(self.model_cfg, bb, self.cfg.max_len)
        if self._prefill_impl != self.cfg.attn_impl:
            self._warn_once(
                "pallas_prefill",
                "attn_impl='pallas' prefill under a mesh runs the XLA "
                "attention path: the prefill kernels are single-device "
                "(decode keeps the shard_map'd kernel)")
        with span("elis.engine.prefill"):
            self.num_prefill_dispatches += 1
            logits, cacheN = self._prefill(self.params, jnp.asarray(toks),
                                           cacheN, jnp.asarray(last_index))
        slots = [s for s, owner in enumerate(self.slot_job)
                 if owner is None][: len(jobs)]
        with span("elis.engine.scatter"):
            # per-row true lengths (prefill stamps the padded length)
            cacheN["len"] = jnp.asarray(
                true_lens + [0] * (bb - len(jobs)), jnp.int32)
            self.cache = self._canon_cache(
                _scatter_slots(self.cache, cacheN, slots, len(jobs)))
        logits_np = self._sync(logits)
        for i, (job, slot) in enumerate(zip(jobs, slots)):
            self.slot_job[slot] = job.job_id
            self.slot_of[job.job_id] = slot
            if job.generated:
                self.last_token[slot, 0] = job.generated[-1]
                # resume recomputes prompt + generated[:-1], and the seed
                # token's KV is written by the first decode step (+1)
                self.resume_context_tokens += true_lens[i] + 1
            else:
                first = int(np.argmax(logits_np[i, -1]))
                self._pending_first[job.job_id] = first
                self.last_token[slot, 0] = first
        return slots

    def evict_job(self, job_id: int) -> None:
        slot = self.slot_of.pop(job_id, None)
        self._pending_first.pop(job_id, None)
        self._prefill_cursor.pop(job_id, None)
        self._chunk_target.pop(job_id, None)
        self._chunk_tokens.pop(job_id, None)
        self._chunk_resumed.pop(job_id, None)
        if slot is not None:
            self.slot_job[slot] = None
            self.last_token[slot, 0] = PAD_ID

    # ------------------------------------------------------------------ #
    def run_window(self, jobs: Sequence[Job], window: int,
                   prefill_chunk: Optional[int] = None
                   ) -> Tuple[List[List[int]], List[bool]]:
        """Execute K decode steps for ``jobs`` (admitting any that lack a
        slot via one batched prefill).  Returns
        (new_tokens_per_job, finished_per_job).

        With ``prefill_chunk`` set (and the family supporting it — see
        :meth:`chunk_supported`), admission becomes *chunked*: new jobs
        claim a slot without prefilling, at most ONE job per window (the
        first incomplete one in batch order) ingests one ``prefill_chunk``-
        sized piece of its prompt, and only fully-prefilled jobs join the
        decode dispatch — a job completing its final chunk in window W
        begins decoding in window W+1.  Mid-prefill jobs emit no tokens.
        Unsupported families fall back loudly to one-shot prefill."""
        if not jobs:
            return [], []
        # swap-in: batch members with a host-stashed cache restore it
        # instead of paying recompute (KV offload tier)
        stashed = [j for j in jobs
                   if not self.has_job(j.job_id) and self.has_stash(j.job_id)]
        if stashed:
            with span("elis.engine.restore", jobs=len(stashed)):
                for job in stashed:
                    self.restore_job(job)
        chunked = prefill_chunk is not None
        if chunked and not self.chunk_supported():
            self._warn_once(
                "chunk_fallback",
                f"prefill_chunk is not supported for "
                f"family={self.model_cfg.family!r} with this cache "
                "(ring/quantized KV or recurrent state); falling back "
                "to one-shot prefill")
            chunked = False
        if chunked:
            fresh = [j for j in jobs if not self.has_job(j.job_id)]
            if fresh:
                with span("elis.engine.admit", jobs=len(fresh)):
                    self._alloc_slots(fresh)
            # decode eligibility is decided BEFORE the chunk runs: the job
            # completing its final chunk this window decodes next window
            incomplete = [j for j in jobs
                          if self.prefill_incomplete(j.job_id)]
            decode_jobs = [j for j in jobs
                           if not self.prefill_incomplete(j.job_id)]
            if incomplete:
                with span("elis.engine.chunk"):
                    self._run_chunk(incomplete[0], prefill_chunk)
        else:
            self.add_jobs(jobs)
            decode_jobs = list(jobs)
        results = {j.job_id: ([], False) for j in jobs}
        if decode_jobs:
            with span("elis.engine.decode", batch=len(decode_jobs)):
                self._decode_jobs(decode_jobs, window, results)
        out_tokens = [list(results[j.job_id][0]) for j in jobs]
        finished = [results[j.job_id][1] for j in jobs]
        # publish each job's materialized context (prompt + generated KV,
        # incl. the seed token) — the scheduler's prefill-debt ranking and
        # the swap-vs-recompute break-even read it
        for job, seq in zip(jobs, out_tokens):
            if self.prefill_incomplete(job.job_id):
                job.prefilled_tokens = self._prefill_cursor[job.job_id]
            else:
                job.prefilled_tokens = (len(job.prompt_tokens)
                                        + job.tokens_generated + len(seq))
        return out_tokens, finished

    def _decode_jobs(self, jobs: Sequence[Job], window: int,
                     results: Dict[int, Tuple[List[int], bool]]) -> None:
        """One masked/compacted decode dispatch for ``jobs`` (all holding
        fully-prefilled slots); writes (tokens, finished) into ``results``."""
        slots = [self.slot_of[job.job_id] for job in jobs]
        prev_lens = self._sync(self.cache["len"]).copy()
        ms = self.cfg.max_slots
        order = sorted(slots)
        db = min(batch_bucket(len(order)), ms)
        compact = self.cfg.masked_decode and db < ms
        with span("elis.engine.gather"):
            if compact:
                # decode only the scheduled slots, padded to the batch bucket
                # (pad rows duplicate a real slot but start dead, so they are
                # frozen no-ops); gather/scatter costs one pass over the active
                # slots' cache per *window*, decode reads it K times
                gidx = np.asarray(order + [order[0]] * (db - len(order)),
                                  np.int32)
                sub_cache = self._canon_cache(
                    _gather_slots(self.cache, jnp.asarray(gidx)))
                sub_last = jnp.asarray(self.last_token[gidx])
                alive0 = np.zeros((db,), bool)
                alive0[: len(order)] = True
                row_of = {slot: r for r, slot in enumerate(order)}
            else:
                sub_cache = self.cache
                sub_last = jnp.asarray(self.last_token)
                if self.cfg.masked_decode:
                    # full-width dispatch, but unscheduled slots stay frozen
                    alive0 = np.zeros((ms,), bool)
                    alive0[slots] = True
                else:
                    # pre-fast-path baseline: every slot advances every window
                    alive0 = np.ones((ms,), bool)
                row_of = {s: s for s in slots}
        with span("elis.engine.decode_run"):
            fn = self._decode_window(window, int(sub_last.shape[0]))
            self._key, sub_key = jax.random.split(self._key)
            self.num_decode_dispatches += 1
            if T.decode_reads_in_place(self.model_cfg, sub_cache,
                                       self.cfg.attn_impl):
                self.num_decode_kv_inplace += 1
            new_cache, toks = fn(self.params, sub_cache, sub_last,
                                 jnp.asarray(alive0), sub_key)
        toks = self._sync(toks)  # (rows, K)
        with span("elis.engine.scatter"):
            if compact:
                self.cache = self._canon_cache(
                    _scatter_slots(self.cache, new_cache, order, len(order)))
            else:
                self.cache = new_cache
        lens = self._sync(self.cache["len"]).copy()
        with span("elis.engine.emit"):
            for job in jobs:
                slot = self.slot_of[job.job_id]
                scanned = toks[row_of[slot]].tolist()
                pending = self._pending_first.pop(job.job_id, None)
                if pending is not None:
                    # first emission comes from the prefill logits; the scan's
                    # K-th token is unconsumed (its cache write is rolled back)
                    seq = [pending] + scanned[: window - 1]
                    consumed_scanned = len(seq) - 1
                else:
                    seq = scanned[:window]
                    consumed_scanned = len(seq)
                cap = self.cfg.max_output
                if self.cfg.respect_job_max and job.true_output_len > 0:
                    cap = min(cap, job.true_output_len)
                if self.cfg.eos_id in seq:
                    cut = seq.index(self.cfg.eos_id) + 1
                    dropped = len(seq) - cut
                    seq = seq[:cut]
                    consumed_scanned -= dropped
                    fin = True
                else:
                    fin = False
                room = cap - job.tokens_generated
                if len(seq) >= room:
                    dropped = len(seq) - room
                    seq = seq[:room]
                    consumed_scanned -= dropped
                    fin = True
                results[job.job_id] = (seq, fin)
                self.last_token[slot, 0] = seq[-1] if seq else PAD_ID
                # the cache pointer advances exactly one position per consumed
                # scan write — robust to both EOS freezing (which already
                # stopped advancing) and cap truncation (which did not)
                lens[slot] = prev_lens[slot] + max(consumed_scanned, 0)
        with span("elis.engine.len_write"):
            self.cache = self._canon_cache(
                {**self.cache, "len": jnp.asarray(lens)})


# --------------------------------------------------------------------------- #
# Backend adapter for the ELIS frontend
# --------------------------------------------------------------------------- #


class EngineExecutor(Backend):
    """Wraps per-node InferenceEngines behind the frontend Backend ABC.
    Durations are measured wall-clock — the live-system evaluation mode.

    Every executed window is appended to ``window_log`` (node, batch,
    window, duration, tokens); ``calibrated_profile()`` fits those samples
    back onto the simulator's latency model so a live run can parameterise
    a :class:`repro.simulate.SimExecutor` (live↔sim calibration)."""

    def __init__(self, engines: Dict[int, InferenceEngine], *,
                 swap_bandwidth_bytes_s: float = 16e9,
                 swap_latency_s: float = 0.0005,
                 swap_pool_tokens: Optional[int] = None):
        self.engines = engines
        if swap_pool_tokens is not None:
            # PreemptionConfig.swap_pool_tokens: per-engine host-stash
            # watermark (None leaves any engine-level setting untouched)
            for eng in engines.values():
                eng.swap_pool_tokens = swap_pool_tokens
        self.window_log: List[Dict] = []
        #: host<->device copy model for the swap-vs-recompute break-even
        #: (``preempt_costs``) — the live copies themselves are measured
        #: wall-clock, these parameterise only the *decision*
        self.swap_bandwidth_bytes_s = swap_bandwidth_bytes_s
        self.swap_latency_s = swap_latency_s
        #: wall-clock seconds spent offloading per node since its last
        #: window — folded into the next window's reported duration so swap
        #: cost is attributed, not lost between windows
        self._pending_swap_s: Dict[int, float] = {}
        self.swapout_tokens = 0
        self.swapin_tokens = 0
        self.n_swapouts = 0
        self.n_swapins = 0
        #: per-node cached calibration fit for ``preempt_costs`` (refit
        #: after every 32 new windows; None until enough data)
        self._fit_cache: Dict[int, Tuple[int, object]] = {}

    def capacity(self, node: int) -> int:
        return self.engines[node].cfg.max_slots

    def free_capacity(self, node: int) -> int:
        return self.engines[node].free_slots()

    def execute(self, node: int, jobs: Sequence[Job], window: int,
                now: float, prefill_chunk: Optional[int] = None
                ) -> ExecResult:
        with span("elis.engine.execute", batch=len(jobs)):
            eng = self.engines[node]
            t0 = time.perf_counter()
            # capacity: evict nothing here — the frontend already chose the
            # batch; engine must have slots for every scheduled job
            needed = sum(1 for job in jobs if not eng.has_job(job.job_id))
            if needed > eng.free_slots():
                raise RuntimeError(
                    f"node {node}: batch needs {needed} free slots, "
                    f"engine has {eng.free_slots()}"
                )
            for j in jobs:
                if eng.has_stash(j.job_id):
                    self.n_swapins += 1
                    self.swapin_tokens += j.prefilled_tokens
            tokens, finished = eng.run_window(jobs, window,
                                              prefill_chunk=prefill_chunk)
            dur = time.perf_counter() - t0
            dur += self._pending_swap_s.pop(node, 0.0)
            self.window_log.append({
                "node": node, "batch": len(jobs), "window": window,
                "duration_s": dur, "tokens": sum(len(t) for t in tokens),
            })
            return ExecResult(dur, tokens, finished)

    def evict(self, node: int, job: Job) -> None:
        eng = self.engines[node]
        eng.drop_stash(job.job_id)
        eng.evict_job(job.job_id)
        job.prefilled_tokens = 0

    # ------------------------------------------------------------------ #
    # KV offload tier (Backend.offload / Backend.restore)
    # ------------------------------------------------------------------ #

    def offload(self, node: int, job: Job) -> bool:
        """Swap the job's slot cache to host memory (preemption that keeps
        the KV).  Wall-clock cost is accumulated into the node's next
        window duration."""
        eng = self.engines[node]
        t0 = time.perf_counter()
        ok = eng.offload_job(job.job_id)
        if ok:
            self._pending_swap_s[node] = (
                self._pending_swap_s.get(node, 0.0)
                + (time.perf_counter() - t0))
            self.swapout_tokens += job.prefilled_tokens
            self.n_swapouts += 1
        return ok

    def restore(self, node: int, job: Job) -> bool:
        """Explicit swap-in (execute() also restores lazily)."""
        eng = self.engines[node]
        if not eng.has_stash(job.job_id):
            return False
        eng.restore_job(job)
        return True

    def preempt_costs(self, node: int, job: Job
                      ) -> Optional[Tuple[float, float]]:
        """(swap_round_trip_s, recompute_s) estimates for preempting
        ``job`` — the ``auto`` :class:`PreemptPolicy` break-even input.
        Swap cost: two host<->device copies of the job's KV footprint at
        the configured bandwidth.  Recompute cost: the job's context
        through the *calibrated* prefill rate (None until enough measured
        windows exist — the caller then falls back to recompute)."""
        n = job.prefilled_tokens
        if n <= 0:
            return None
        eng = self.engines[node]
        mc = eng.model_cfg
        kv_bytes = (2 * mc.n_layers * (mc.n_kv_heads or mc.n_heads)
                    * mc.head_dim * jnp.dtype(mc.dtype).itemsize)
        swap_s = 2.0 * (self.swap_latency_s
                        + n * kv_bytes / self.swap_bandwidth_bytes_s)
        prof = self._cached_fit(node)
        if prof is None:
            return None
        rec_s = prof.prefill_ms(1, n) / 1000.0
        return swap_s, rec_s

    def _cached_fit(self, node: int):
        n_log = len(self.window_log)
        cached = self._fit_cache.get(node)
        if cached is not None and n_log - cached[0] < 32:
            return cached[1]
        try:
            prof = self.calibrated_profile(nodes=[node])
        except ValueError:
            prof = None
        self._fit_cache[node] = (n_log, prof)
        return prof

    # ------------------------------------------------------------------ #
    def node_counters(self) -> Dict[int, Dict[str, int]]:
        """Per-node compile/dispatch counters — a recompile storm (or
        dead-FLOPs regression) on one pod must be attributable to that pod,
        not smeared across the aggregate."""
        windows = {n: 0 for n in self.engines}
        for rec in self.window_log:
            windows[rec["node"]] = windows.get(rec["node"], 0) + 1
        return {
            n: {"prefill_traces": eng.num_prefill_traces,
                "prefill_dispatches": eng.num_prefill_dispatches,
                "decode_traces": eng.num_decode_traces,
                "decode_dispatches": eng.num_decode_dispatches,
                "decode_kv_inplace": eng.num_decode_kv_inplace,
                "chunk_traces": eng.num_chunk_traces,
                "chunk_dispatches": eng.num_chunk_dispatches,
                "resume_context_tokens": eng.resume_context_tokens,
                "host_syncs": eng.num_host_syncs,
                "windows_executed": windows.get(n, 0)}
            for n, eng in self.engines.items()
        }

    def counters(self) -> Dict[str, int]:
        """Aggregated compile/dispatch counters across this executor's
        engines (the recompile-storm / dead-FLOPs introspection hooks);
        :meth:`node_counters` keeps the per-pod breakdown."""
        agg = {"prefill_traces": 0, "prefill_dispatches": 0,
               "decode_traces": 0, "decode_dispatches": 0,
               "decode_kv_inplace": 0,
               "chunk_traces": 0, "chunk_dispatches": 0,
               "resume_context_tokens": 0, "host_syncs": 0,
               "windows_executed": len(self.window_log),
               "swapouts": self.n_swapouts, "swapins": self.n_swapins,
               "swapout_tokens": self.swapout_tokens,
               "swapin_tokens": self.swapin_tokens,
               "stash_evictions": sum(e.n_stash_evictions
                                      for e in self.engines.values()),
               "stash_evicted_tokens": sum(e.stash_evicted_tokens
                                           for e in self.engines.values())}
        for per in self.node_counters().values():
            for k in ("prefill_traces", "prefill_dispatches",
                      "decode_traces", "decode_dispatches",
                      "decode_kv_inplace",
                      "chunk_traces", "chunk_dispatches",
                      "resume_context_tokens", "host_syncs"):
                agg[k] += per[k]
        return agg

    def calibrated_profile(self, name: str = "live-calibrated",
                           params_b: Optional[float] = None,
                           preempt_batch: int = 64,
                           mem_limit_frac: float = 0.4,
                           nodes: Optional[Sequence[int]] = None):
        """Fit the simulator's latency model to the measured windows.

        The model (``repro.simulate.profiles``):
            duration ≈ overhead + window · d1 · (1 + slowdown · (batch-1))
        is linear in (overhead, d1, d1·slowdown); a least-squares fit over
        ``window_log`` (dropping each (node, batch, window) shape's first
        occurrence, which pays XLA compile) recovers ``decode_ms_1`` and
        ``batch_slowdown``.  Returns a :class:`ModelProfile` usable by
        ``SimExecutor`` — simulate *this* live engine at cluster scale.

        ``nodes`` restricts the fit to a node subset — on a heterogeneous
        pod fleet (different TP degrees / hardware) each pod gets its own
        profile; :meth:`calibrated_node_profiles` fits all of them.
        """
        from repro.simulate.profiles import (CALIBRATION_MEAN_TOKENS,
                                             ModelProfile)
        keep = set(self.engines if nodes is None else nodes)
        unknown = keep - set(self.engines)
        if unknown:
            raise ValueError(
                f"calibrated_profile: unknown node(s) {sorted(unknown)}; "
                f"this executor drives nodes {sorted(self.engines)}")
        log = [rec for rec in self.window_log if rec["node"] in keep]
        seen = set()
        samples = []
        for rec in log:
            key = (rec["node"], rec["batch"], rec["window"])
            if key in seen:
                samples.append(rec)
            else:
                seen.add(key)  # first occurrence pays compile — drop it
        if not samples:
            samples = list(log)
        if not samples:
            raise ValueError(
                "calibrated_profile: window_log holds no executed windows "
                f"for node(s) {sorted(keep)} — run at least one window via "
                "execute() before calibrating")
        w = np.array([r["window"] for r in samples], float)
        b = np.array([r["batch"] for r in samples], float)
        d = np.array([r["duration_s"] for r in samples], float)
        X = np.stack([np.ones_like(w), w, w * (b - 1)], axis=1)
        if np.linalg.matrix_rank(X) >= 3:
            (o, a, c), *_ = np.linalg.lstsq(X, d, rcond=None)
            a = float(max(a, 1e-9))
            slowdown = float(min(max(c / a, 0.0), 10.0))
            overhead = float(max(o, 0.0))
        else:
            # degenerate design (single batch size or window length):
            # attribute everything to the per-token rate
            a = float(max(np.mean(d / np.maximum(w, 1.0)), 1e-9))
            slowdown = 0.0
            overhead = 0.0
        #: per-window fixed cost (dispatch + host loop) the latency model's
        #: intercept absorbed — feed it to SimExecutor.sched_overhead_s so
        #: a calibrated replay prices whole windows, not just tokens
        self.fit_overhead_s = overhead
        eng = self.engines[min(keep)]
        mc = eng.model_cfg
        if params_b is None:
            # rough dense-transformer parameter count from the config
            params_b = 12 * mc.n_layers * mc.d_model ** 2 / 1e9
        return ModelProfile(
            name=name, params_b=params_b,
            avg_latency_ms=a * 1000.0 * CALIBRATION_MEAN_TOKENS,
            n_layers=mc.n_layers,
            n_kv_heads=mc.n_kv_heads or mc.n_heads,
            head_dim=mc.head_dim,
            preempt_batch=preempt_batch, mem_limit_frac=mem_limit_frac,
            batch_slowdown=slowdown,
        )

    def calibrated_node_profiles(self, prefix: str = "live-node", **kw
                                 ) -> Dict[int, "object"]:
        """Per-pod live fits: {node: ModelProfile}.  Also records each
        pod's fitted per-window overhead in ``node_fit_overhead_s`` (feed
        the mean to ``SimExecutor.sched_overhead_s`` for a replay that
        prices whole windows)."""
        profs, over = {}, {}
        for n in sorted(self.engines):
            profs[n] = self.calibrated_profile(name=f"{prefix}{n}",
                                               nodes=[n], **kw)
            over[n] = self.fit_overhead_s
        self.node_fit_overhead_s = over
        return profs

    def node_token_cost(self) -> Dict[int, float]:
        """Fitted seconds-per-token per node — the ``least_eta`` placement
        input, measured from this executor's own window log instead of
        assumed uniform."""
        return {n: p.decode_ms_1 / 1000.0
                for n, p in self.calibrated_node_profiles().items()}


# --------------------------------------------------------------------------- #
# Data-parallel pod construction
# --------------------------------------------------------------------------- #


def make_tp_pods(model_cfg, params, cfg: Optional[EngineConfig] = None, *,
                 n_pods: int = 1, tp: int = 1, devices=None
                 ) -> Dict[int, InferenceEngine]:
    """Build ``n_pods`` data-parallel serving pods, each a ``tp``-way
    tensor-parallel :class:`InferenceEngine` on its own **disjoint**
    single-axis ``("model",)`` mesh — the live-cluster topology the
    frontend's placement policies drive (each pod registers as one node in
    ``GlobalState``; no collective ever crosses pods).

    One host copy of ``params`` is device_put onto every pod's devices.
    ``tp=1`` pods are single-device engines (no mesh, no collective
    overhead), pod ``n`` committed to device ``n``."""
    from repro.launch.mesh import make_mesh
    devices = list(jax.devices() if devices is None else devices)
    need = n_pods * tp
    if len(devices) < need:
        raise RuntimeError(
            f"{n_pods} pods x TP={tp} need {need} devices, have "
            f"{len(devices)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    if tp <= 1:
        return {n: InferenceEngine(model_cfg, params, cfg, device=devices[n])
                for n in range(n_pods)}
    return {
        n: InferenceEngine(
            model_cfg, params, cfg,
            mesh=make_mesh((tp,), ("model",),
                           devices=devices[n * tp:(n + 1) * tp]))
        for n in range(n_pods)
    }
