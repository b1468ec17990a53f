"""Core transformer building blocks (pure JAX, pytree params).

All parameters are plain nested dicts of ``jnp.ndarray`` so they shard
naturally under pjit and stack naturally under ``lax.scan``.  Every block is
expressed as an ``init_*`` function (returns the param subtree) plus an apply
function (pure, takes the subtree first).

Attention supports:
  * GQA (n_kv_heads < n_heads) via broadcast within the head-group axis,
  * causal masks with query offsets (decode),
  * sliding-window (SWA) masks,
  * ring-buffer KV caches for bounded-window decode (long_500k carve-in),
  * dispatch to the Pallas kernels (``attn_impl="pallas"``) or the pure-XLA
    einsum path (``attn_impl="xla"``, the oracle).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, jnp.ndarray]


@jax.tree_util.register_pytree_node_class
class KVCache:
    """K/V buffers with *static* ring-buffer and quantization flags.

    ``ring``/``quantized`` are pytree aux-data (not leaves), so they stay
    Python bools under ``jit`` — resolved at trace time.
    Buffers are (..., batch, buf_len, kv_heads, head_dim); a leading layer/
    site axis is present in the stacked model cache and absent inside a
    per-layer scan body.

    Quantized mode (int8 KV — beyond-paper §Perf optimization): buffers are
    int8 with per-(batch, slot) fp32 scales ``k_scale``/``v_scale`` of shape
    (..., batch, buf_len); values are symmetric-quantized at write
    (scale = amax/127 over the token's heads×dims) and dequantized fused
    into the attention read.
    """

    def __init__(self, k, v, ring: bool = False, k_scale=None, v_scale=None):
        self.k = k
        self.v = v
        self.ring = ring
        self.k_scale = k_scale
        self.v_scale = v_scale

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def tree_flatten(self):
        # fixed 4-child arity — None scales are empty subtrees, so the
        # treedef stays consistent when JAX reconstructs with placeholders
        return (self.k, self.v, self.k_scale, self.v_scale), self.ring

    @classmethod
    def tree_unflatten(cls, ring, children):
        k, v, ks, vs = children
        return cls(k, v, ring, ks, vs)

    def __repr__(self):
        return (f"KVCache(k={getattr(self.k, 'shape', None)}, "
                f"ring={self.ring}, quantized={self.quantized})")


def quantize_kv(x: jnp.ndarray):
    """x (B, S, KH, D) -> (int8 values, fp32 scales (B, S))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-1, -2))
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None, None]).astype(dtype)


def masked_span_write(buf: jnp.ndarray, start: jnp.ndarray, val: jnp.ndarray,
                      valid_len: jnp.ndarray) -> jnp.ndarray:
    """Write ``val`` (B, C, ...) into ``buf`` (B, L, ...) at rows
    ``[start, start + valid_len)`` per batch element.  Positions beyond
    ``valid_len`` (chunk padding) are dropped via out-of-bounds scatter
    (``mode="drop"``), so the existing buffer content there stays
    bit-identical — the chunked-prefill analogue of
    :func:`masked_row_write`'s frozen-slot no-op."""
    b, c = val.shape[0], val.shape[1]
    idx = start[:, None] + jnp.arange(c)[None, :]          # (B, C)
    ok = jnp.arange(c)[None, :] < valid_len[:, None]
    safe = jnp.where(ok, idx, buf.shape[1])                # OOB -> dropped
    rows = jnp.arange(b)[:, None]
    return buf.at[rows, safe].set(val, mode="drop")


def masked_row_write(buf: jnp.ndarray, slot: jnp.ndarray, val: jnp.ndarray,
                     active=None, layer=None) -> jnp.ndarray:
    """Write ``val`` (B, ...) into ``buf`` (B, L, ...) at per-row position
    ``slot`` (B,).  Rows with ``active=False`` keep their previous value —
    the no-op that lets frozen decode slots (EOS-finished or simply
    unoccupied) share a dispatch with live slots without corrupting their
    cache.  The select touches one position per row, so the masked write
    costs a (B, ...) gather, not a whole-buffer copy.  With ``layer``,
    ``buf`` is the stacked (n_layers, B, L, ...) cache and the rows land
    at ``[layer, b, slot[b]]``: a scatter XLA does in place when ``buf``
    is a loop carry."""
    rows = jnp.arange(val.shape[0])
    idx = (rows, slot) if layer is None else (layer, rows, slot)
    if active is not None:
        keep = active.reshape((-1,) + (1,) * (val.ndim - 1))
        val = jnp.where(keep, val, buf[idx])
    return buf.at[idx].set(val)


def kernel_reads_cache(cache: KVCache, attn_impl: str) -> bool:
    """Whether a decode step's attention read is the flash-decode kernel,
    which reads a stacked cache in place at a layer index; the ring (SWA)
    and int8 reads, and ``attn_impl="xla"``, take the layer's slice."""
    return attn_impl == "pallas" and not cache.ring and not cache.quantized


# --------------------------------------------------------------------------- #
# Initialisers
# --------------------------------------------------------------------------- #


def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return jax.random.uniform(key, (in_dim, out_dim), dtype, -scale, scale)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32) -> jnp.ndarray:
    return jax.random.normal(key, (vocab, dim), dtype) * 0.02


# --------------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------------- #


def init_rmsnorm(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    orig = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(orig)


def init_layernorm(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    orig = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    out = x * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(orig)


def apply_norm(cfg, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.norm == "layernorm":
        return layernorm(p, x, cfg.norm_eps)
    return rmsnorm(p, x, cfg.norm_eps)


def init_norm(cfg, dim: int, dtype=jnp.float32) -> Params:
    if cfg.norm == "layernorm":
        return init_layernorm(dim, dtype)
    return init_rmsnorm(dim, dtype)


# --------------------------------------------------------------------------- #
# Rotary position embeddings (RoPE and M-RoPE)
# --------------------------------------------------------------------------- #


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape (head_dim // 2,)."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float):
    """positions (..., seq) -> cos/sin of shape (..., seq, head_dim//2)."""
    inv = rope_freqs(head_dim, theta)
    angles = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(angles), jnp.sin(angles)


def mrope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float,
                  sections: Tuple[int, int, int]):
    """M-RoPE (Qwen2-VL): 3D positions (3, batch, seq); frequency bands are
    partitioned into (temporal, height, width) sections.  Returns cos/sin of
    shape (batch, seq, head_dim//2)."""
    inv = rope_freqs(head_dim, theta)  # (half,)
    # angles per axis: (3, batch, seq, half)
    angles = positions[..., None].astype(jnp.float32) * inv
    half = head_dim // 2
    t, h, w = sections
    assert t + h + w == half, (sections, half)
    sel = jnp.concatenate(
        [jnp.zeros((t,), jnp.int32), jnp.ones((h,), jnp.int32),
         jnp.full((w,), 2, jnp.int32)]
    )  # (half,) in {0,1,2}
    # gather: angles is (3, B, S, half); choose axis sel[i] for frequency i
    picked = angles[sel, ..., jnp.arange(half)]  # (half, B, S)
    picked = jnp.moveaxis(picked, 0, -1)  # (B, S, half)
    return jnp.cos(picked), jnp.sin(picked)


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x (B, S, H, D); cos/sin (B, S, D//2). Rotate-half convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)


def positional_cos_sin(cfg, positions: jnp.ndarray):
    """positions: (B, S) for rope / (3, B, S) for mrope -> (cos, sin) or None."""
    if cfg.rope_type == "rope":
        return rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    if cfg.rope_type == "mrope":
        if positions.ndim == 2:  # text-only fallback: replicate across axes
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return mrope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                             cfg.mrope_sections)
    return None


# --------------------------------------------------------------------------- #
# Attention
# --------------------------------------------------------------------------- #


def init_attention(key, cfg, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    hd = cfg.head_dim
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


# --------------------------------------------------------------------------- #
# Activation-sharding hints (§Perf)
# --------------------------------------------------------------------------- #

_ATTN_HEAD_AXIS = None


class attn_head_sharding:
    """Context: constrain q/k/v activations to head-sharding on the given
    mesh axis (padded when n_heads isn't divisible).  Fixes the GSPMD
    pathology where flat-projection shards straddle head boundaries and the
    partitioner all-reduces partial attention scores (see EXPERIMENTS.md
    §Perf HC2: a 120 GB/step all-reduce of f32[B,H,32k,32k])."""

    def __init__(self, axis: str = "model"):
        self.axis = axis

    def __enter__(self):
        global _ATTN_HEAD_AXIS
        self._prev = _ATTN_HEAD_AXIS
        _ATTN_HEAD_AXIS = self.axis
        return self

    def __exit__(self, *a):
        global _ATTN_HEAD_AXIS
        _ATTN_HEAD_AXIS = self._prev


def _constrain_heads(x: jnp.ndarray) -> jnp.ndarray:
    """x (B, S, H, D) -> head-sharded on the hinted axis (no-op otherwise)."""
    if _ATTN_HEAD_AXIS is None:
        return x
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(
        x, P(None, None, _ATTN_HEAD_AXIS, None)
    )


def _expand_kv(k: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """(B, S, KH, D) -> (B, S, H, D) by repeating each kv head H/KH times."""
    b, s, kh, d = k.shape
    rep = n_heads // kh
    if rep == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, rep, d))
    return k.reshape(b, s, n_heads, d)


def sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset=0,
    kv_len=None,
    window: Optional[int] = None,
    ring_offset=None,
) -> jnp.ndarray:
    """Scaled dot-product attention with GQA, decode offsets and SWA.

    q: (B, Sq, H, D); k/v: (B, Skv, KH, D).
    q_offset: absolute position of q[0] (scalar; decode passes cur_len).
    kv_len: number of valid cache entries (scalar) — positions >= kv_len masked.
    window: sliding-window size; keys older than (q_pos - window + 1) masked.
    ring_offset: if the KV buffer is a ring buffer, absolute position of
      buffer slot 0 is ``ring_offset`` — key absolute positions are
      ``ring_offset + ((slot - ring_offset) mod Skv)``... we instead pass the
      precomputed absolute key positions directly when ringed (see caller).
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    if sq > 1:
        # full-sequence (prefill/train) only: constraining the cache-sized
        # K/V of a one-token decode forces a whole-cache reshard per layer
        # per step (measured 25x collective regression — EXPERIMENTS §Perf)
        q = _constrain_heads(q)
        k = _constrain_heads(_expand_kv(k, h))
        v = _constrain_heads(_expand_kv(v, h))
    else:
        k = _expand_kv(k, h)
        v = _expand_kv(v, h)
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale

    # q_offset may be scalar or per-batch (B,) — decode slots advance
    # independently under continuous batching
    q_off = jnp.asarray(q_offset)
    q_pos = jnp.arange(sq)[None, :] + q_off.reshape(-1, 1)  # (1|B, Sq)
    q_pos = q_pos[:, None, :, None]  # (1|B, 1, Sq, 1)
    if ring_offset is not None:
        k_pos = ring_offset  # precomputed absolute positions (Skv,) or (B,Skv)
        if k_pos.ndim == 1:
            k_pos = k_pos[None, :]
        k_pos = k_pos[:, None, None, :]  # (B,1,1,Skv)
    else:
        k_pos = jnp.arange(skv)[None, None, None, :]

    mask = jnp.ones_like(scores, dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if kv_len is not None:
        valid = jnp.arange(skv)[None, None, None, :] < jnp.asarray(kv_len).reshape(-1, 1, 1, 1)
        mask &= valid
    if window is not None:
        mask &= k_pos > (q_pos - window)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (can happen with ring padding) -> zeros not nans
    probs = jnp.where(jnp.any(mask, axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_block(
    p: Params,
    cfg,
    x: jnp.ndarray,
    cos_sin,
    *,
    cache: Optional[KVCache] = None,
    cur_index=None,
    attn_impl: str = "xla",
    active=None,
    valid_len=None,
    mesh=None,
    layer=None,
) -> Tuple[jnp.ndarray, object]:
    """Full attention block: proj -> rope -> (cache update) -> sdpa -> out proj.

    Training/prefill: ``cache is None`` -> full-sequence causal attention,
    returns (out, (k, v)) for cache seeding.
    Decode: ``cache`` is a :class:`KVCache` with buffers (B, L, KH, D) and
    ``cur_index`` is the per-slot token count; x is (B, 1, d_model).
    ``active`` (B,) bool, decode only: rows marked inactive skip their KV
    write (their buffer row is bit-identical afterwards) — the caller
    freezes their ``len`` to match, so a frozen slot's cache is untouched
    by the dispatch it shared with live slots.
    Tensor-parallel decode: ``mesh`` (a single-axis ``("model",)`` mesh)
    makes the ``attn_impl="pallas"`` decode read run the flash-decode
    kernel ``shard_map``-ped over the model axis — Q/KV heads partitioned
    exactly as ``engine_shardings`` places them, per-slot lengths
    replicated, no collective inside the kernel (docs/kernels.md).
    Callers must only pass a mesh when both head axes divide it.
    ``layer`` (traced scalar), decode only: ``cache`` is the whole stacked
    (n_layers, B, L, KH, D) cache, carried through the layer loop; this
    layer's row is written in place at ``[layer, b, slot]``, the kernel
    reads the stack at ``layer`` (:func:`kernel_reads_cache`), the other
    reads take ``buf[layer]``, and the stacked cache is returned.
    Chunked prefill: ``cache`` given AND x is (B, C>1, d) — the C fresh
    tokens start at absolute position ``cur_index`` (B,) and only the first
    ``valid_len`` (B,) of them are real (the rest is bucket padding).  The
    valid span's K/V are span-written into the buffer and the chunk's
    queries attend over the whole buffer under a ``kv_len`` mask — masked
    positions contribute exactly +0.0 after softmax (the same invariant
    batched bucketed prefill already relies on), so chunked prefill is
    bit-identical to one-shot prefill.  Ring/quantized caches are rejected:
    a ring write is position-destructive and a quantized read would
    dequantize the prefix while one-shot prefill attends the unquantized
    fresh K/V, breaking bit-identity.
    """
    b, s, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kh, hd)
    v = v.reshape(b, s, kh, hd)
    if cos_sin is not None:
        cos, sin = cos_sin
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    if s > 1:
        q = _constrain_heads(q)

    window = cfg.swa_window if cfg.attention_type == "swa" else None

    if cache is None:
        if attn_impl == "pallas" and s > 1:
            from repro.kernels import ops as kops

            out = kops.flash_attention(q, k, v, causal=True, window=window)
        else:
            out = sdpa(q, k, v, causal=True, window=window)
        new_kv = (k, v)
    else:
        kbuf, vbuf = cache.k, cache.v
        L = kbuf.shape[-3]
        ringed = cache.ring
        # cur_index may be scalar or per-batch (B,) under continuous batching
        cur = jnp.broadcast_to(jnp.asarray(cur_index), (b,))
        if s > 1:
            # chunked prefill over a partially-filled cache (see docstring)
            if ringed or cache.quantized:
                raise ValueError(
                    "chunked prefill requires a dense unquantized KV cache "
                    "(ring/SWA and int8 caches fall back to one-shot prefill)")
            valid = jnp.broadcast_to(
                jnp.asarray(s if valid_len is None else valid_len), (b,))
            kbuf = masked_span_write(kbuf, cur, k, valid)
            vbuf = masked_span_write(vbuf, cur, v, valid)
            out = sdpa(q, kbuf, vbuf, causal=True, q_offset=cur,
                       kv_len=cur + valid, window=window)
            out = out.reshape(b, s, h * hd)
            return out @ p["wo"], KVCache(kbuf, vbuf, False)
        slot = cur % L if ringed else cur
        if cache.quantized:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            kbuf = masked_row_write(kbuf, slot, kq[:, 0], active, layer)
            vbuf = masked_row_write(vbuf, slot, vq[:, 0], active, layer)
            k_sc = masked_row_write(cache.k_scale, slot, ks[:, 0], active,
                                    layer)
            v_sc = masked_row_write(cache.v_scale, slot, vs[:, 0], active,
                                    layer)
        else:
            kbuf = masked_row_write(kbuf, slot, k[:, 0], active, layer)
            vbuf = masked_row_write(vbuf, slot, v[:, 0], active, layer)
            k_sc = v_sc = None
        if kernel_reads_cache(cache, attn_impl):
            from repro.kernels import ops as kops

            out = kops.flash_decode(q, kbuf, vbuf, kv_len=cur + 1,
                                    q_offset=cur, window=window, mesh=mesh,
                                    layer=0 if layer is None else layer)
        else:
            def at_layer(buf):
                return buf if layer is None else buf[layer]

            kread, vread = at_layer(kbuf), at_layer(vbuf)
            if cache.quantized:
                kread = dequantize_kv(kread, at_layer(k_sc), q.dtype)
                vread = dequantize_kv(vread, at_layer(v_sc), q.dtype)
            if ringed:
                # absolute position of each buffer slot given cur tokens
                # seen: slot i holds the largest position p <= cur with
                # p % L == i
                idx = jnp.arange(L)[None, :]
                k_pos = idx + ((cur[:, None] - idx) // L) * L
                k_pos = jnp.where(k_pos < 0, -1_000_000_000, k_pos)
                out = sdpa(q, kread, vread, causal=True, q_offset=cur,
                           window=window, ring_offset=k_pos)
            else:
                out = sdpa(q, kread, vread, causal=True, q_offset=cur,
                           kv_len=cur + 1, window=window)
        new_kv = KVCache(kbuf, vbuf, ringed, k_sc, v_sc)
    out = out.reshape(b, s, h * hd)
    return out @ p["wo"], new_kv


def init_cross_attention(key, cfg, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, d, dtype),
        "wk": dense_init(ks[1], d, d, dtype),
        "wv": dense_init(ks[2], d, d, dtype),
        "wo": dense_init(ks[3], d, d, dtype),
    }


def cross_attention_block(p: Params, cfg, x: jnp.ndarray,
                          enc_kv: Tuple[jnp.ndarray, jnp.ndarray]) -> jnp.ndarray:
    """Decoder cross-attention over precomputed encoder K/V."""
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k, v = enc_kv
    out = sdpa(q, k, v, causal=False)
    return out.reshape(b, s, d) @ p["wo"]


def encode_cross_kv(p: Params, cfg, enc_out: jnp.ndarray):
    b, se, d = enc_out.shape
    h, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    k = (enc_out @ p["wk"]).reshape(b, se, h, hd)
    v = (enc_out @ p["wv"]).reshape(b, se, h, hd)
    return k, v


# --------------------------------------------------------------------------- #
# MLP
# --------------------------------------------------------------------------- #


def init_mlp(key, d_model: int, d_ff: int, gated: bool, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(ks[0], d_model, d_ff, dtype),
        "w_down": dense_init(ks[1], d_ff, d_model, dtype),
    }
    if gated:
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def mlp_block(p: Params, cfg, x: jnp.ndarray) -> jnp.ndarray:
    if "w_gate" in p:
        act = jax.nn.silu(x @ p["w_gate"]) if cfg.activation == "silu" else jax.nn.gelu(x @ p["w_gate"])
        return (act * (x @ p["w_up"])) @ p["w_down"]
    h = x @ p["w_up"]
    h = jax.nn.gelu(h) if cfg.activation == "gelu" else jax.nn.silu(h)
    return h @ p["w_down"]
