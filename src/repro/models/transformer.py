"""Model assembly for all assigned architecture families.

Families and their block structure:
  dense / vlm        : [norm -> GQA attn -> norm -> gated MLP] x L
  moe                : [norm -> GQA attn -> norm -> MoE FFN] x L
  ssm                : [norm -> Mamba2] x L
  hybrid (zamba2)    : 13 groups of (6 x [norm -> Mamba2]) each followed by a
                       weight-SHARED attention block, + 3 tail Mamba2 layers
  audio (whisper)    : encoder stack over stub frame embeddings + decoder with
                       self- and cross-attention, learned positions, LayerNorm

All layer stacks are ``lax.scan``-stacked: parameters carry a leading layer
axis, which keeps HLO size (and 512-way SPMD compile time) bounded.

Public API:
  init_params(key, cfg)                       -> params pytree
  forward(params, cfg, batch)                 -> (logits, aux_loss)
  init_cache(cfg, batch, max_len, dtype)      -> cache pytree
  prefill(params, cfg, batch, cache)          -> (last_logits, cache)
  decode_step(params, cfg, tokens, cache)     -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.scanning import layer_scan

Params = Dict[str, Any]
Cache = Dict[str, Any]


KVCache = L.KVCache


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


# =========================================================================== #
# Init
# =========================================================================== #


def _init_dense_layer(key, cfg, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": L.init_norm(cfg, cfg.d_model, dtype),
        "attn": L.init_attention(k1, cfg, dtype),
        "mlp_norm": L.init_norm(cfg, cfg.d_model, dtype),
    }
    if cfg.moe.enabled:
        p["moe"] = M.init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)
    return p


def _init_ssm_layer(key, cfg, dtype) -> Params:
    return {
        "norm": L.init_norm(cfg, cfg.d_model, dtype),
        "ssm": S.init_ssm(key, cfg, dtype),
    }


def _stack_init(fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


def hybrid_layout(cfg) -> Tuple[int, int, int]:
    """(n_groups, inner_per_group, n_tail) for the hybrid family."""
    every = cfg.hybrid.attn_every
    n_groups = cfg.n_layers // every
    tail = cfg.n_layers - n_groups * every
    return n_groups, every, tail


def init_params(key, cfg) -> Params:
    dtype = _dtype(cfg)
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": L.embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": L.init_norm(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[1], cfg.d_model, cfg.vocab_size,
                                         dtype)

    if cfg.family in ("dense", "moe", "vlm"):
        params["layers"] = _stack_init(
            lambda k: _init_dense_layer(k, cfg, dtype), keys[2], cfg.n_layers
        )
    elif cfg.family == "ssm":
        params["layers"] = _stack_init(
            lambda k: _init_ssm_layer(k, cfg, dtype), keys[2], cfg.n_layers
        )
    elif cfg.family == "hybrid":
        n_groups, inner, tail = hybrid_layout(cfg)
        grp_keys = jax.random.split(keys[2], n_groups)
        params["groups"] = jax.vmap(
            lambda k: _stack_init(lambda kk: _init_ssm_layer(kk, cfg, dtype),
                                  k, inner)
        )(grp_keys)
        if tail:
            params["tail"] = _stack_init(
                lambda k: _init_ssm_layer(k, cfg, dtype), keys[3], tail
            )
        params["shared_attn"] = _init_dense_layer(keys[4], cfg, dtype)
    elif cfg.family == "audio":
        enc = cfg.encoder
        params["enc_pos"] = L.embed_init(keys[3], enc.n_frames, cfg.d_model,
                                         dtype)
        params["pos_embed"] = L.embed_init(
            keys[4], cfg.max_position_embeddings, cfg.d_model, dtype
        )

        def init_enc_layer(k):
            k1, k2 = jax.random.split(k)
            return {
                "attn_norm": L.init_norm(cfg, cfg.d_model, dtype),
                "attn": L.init_attention(k1, cfg, dtype),
                "mlp_norm": L.init_norm(cfg, cfg.d_model, dtype),
                "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.gated_mlp,
                                  dtype),
            }

        def init_dec_layer(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {
                "attn_norm": L.init_norm(cfg, cfg.d_model, dtype),
                "attn": L.init_attention(k1, cfg, dtype),
                "cross_norm": L.init_norm(cfg, cfg.d_model, dtype),
                "cross": L.init_cross_attention(k2, cfg, dtype),
                "mlp_norm": L.init_norm(cfg, cfg.d_model, dtype),
                "mlp": L.init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.gated_mlp,
                                  dtype),
            }

        params["enc_layers"] = _stack_init(init_enc_layer, keys[5],
                                           enc.n_layers)
        params["enc_final_norm"] = L.init_norm(cfg, cfg.d_model, dtype)
        params["layers"] = _stack_init(init_dec_layer, keys[6], cfg.n_layers)
    else:
        raise ValueError(cfg.family)
    return params


# =========================================================================== #
# Embedding / unembedding
# =========================================================================== #


def embed_inputs(params: Params, cfg, batch: Dict) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (hidden (B,S,d), positions (B,S) or (3,B,S))."""
    tokens = batch["tokens"]
    h = params["embed"][tokens]
    if cfg.frontend == "vision_stub" and "embeds" in batch:
        h = jnp.concatenate([batch["embeds"].astype(h.dtype), h], axis=1)
    s = h.shape[1]
    if "positions" in batch and batch["positions"] is not None:
        pos = batch["positions"]
    else:
        pos = jnp.broadcast_to(jnp.arange(s)[None, :], (h.shape[0], s))
    if cfg.rope_type == "learned":
        h = h + params["pos_embed"][pos]
    return h, pos


def unembed(params: Params, cfg, h: jnp.ndarray) -> jnp.ndarray:
    h = L.apply_norm(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        return h @ params["embed"].T
    return h @ params["lm_head"]


# =========================================================================== #
# Layer bodies
# =========================================================================== #


def _dense_body(cfg, attn_impl, moe_impl, lp: Params, x, cos_sin,
                cache=None, cur_index=None, active=None, valid_len=None,
                mesh=None):
    h = L.apply_norm(cfg, lp["attn_norm"], x)
    attn_out, kv = L.attention_block(
        lp["attn"], cfg, h, cos_sin, cache=cache, cur_index=cur_index,
        attn_impl=attn_impl, active=active, valid_len=valid_len, mesh=mesh,
    )
    x = x + attn_out
    h = L.apply_norm(cfg, lp["mlp_norm"], x)
    if cfg.moe.enabled:
        out, aux = M.moe_block(lp["moe"], cfg, h, impl=moe_impl)
    else:
        out, aux = L.mlp_block(lp["mlp"], cfg, h), jnp.float32(0)
    return x + out, kv, aux


def _ssm_body(cfg, impl, lp: Params, x, state=None, active=None):
    h = L.apply_norm(cfg, lp["norm"], x)
    if state is None:
        out, _ = S.ssm_forward(lp["ssm"], cfg, h, impl=impl)
        return x + out, None
    out, new_state = S.ssm_decode_step(lp["ssm"], cfg, h, state)
    if active is not None:
        # frozen decode slots keep their recurrent state bit-identical
        new_state = jax.tree_util.tree_map(
            lambda old, new: jnp.where(
                active.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
            state, new_state)
    return x + out, new_state


# =========================================================================== #
# Forward (training / full-sequence)
# =========================================================================== #


def forward(params: Params, cfg, batch: Dict, *, attn_impl: str = "xla",
            moe_impl: str = "dense",
            remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Teacher-forced full-sequence forward.  Returns (logits, aux_loss).

    ``remat=True`` rematerialises each scanned layer body on the backward
    pass — only the per-layer residual stream is saved (training memory).
    """
    ckpt = (lambda f: jax.checkpoint(f, prevent_cse=False)) if remat else (
        lambda f: f)
    h, pos = embed_inputs(params, cfg, batch)
    cos_sin = L.positional_cos_sin(cfg, pos) if cfg.rope_type in ("rope", "mrope") else None

    if cfg.family in ("dense", "moe", "vlm"):
        @ckpt
        def body(carry, lp):
            x, aux = carry
            x, _, a = _dense_body(cfg, attn_impl, moe_impl, lp, x, cos_sin)
            return (x, aux + a), None

        (h, aux), _ = layer_scan(body, (h, jnp.float32(0)), params["layers"])
    elif cfg.family == "ssm":
        @ckpt
        def body(x, lp):
            x, _ = _ssm_body(cfg, attn_impl, lp, x)
            return x, None

        h, _ = layer_scan(body, h, params["layers"])
        aux = jnp.float32(0)
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        @ckpt
        def inner(x, lp):
            x, _ = _ssm_body(cfg, attn_impl, lp, x)
            return x, None

        @ckpt
        def group(x, gp):
            x, _ = layer_scan(inner, x, gp)
            x, _, _ = _dense_body(cfg, attn_impl, moe_impl, shared, x, cos_sin)
            return x, None

        h, _ = layer_scan(group, h, params["groups"])
        if "tail" in params:
            h, _ = layer_scan(inner, h, params["tail"])
        aux = jnp.float32(0)
    elif cfg.family == "audio":
        enc_out = encode_audio(params, cfg, batch["frames"], attn_impl,
                               remat=remat)

        @ckpt
        def body(x, lp):
            hh = L.apply_norm(cfg, lp["attn_norm"], x)
            attn_out, _ = L.attention_block(lp["attn"], cfg, hh, None,
                                            attn_impl=attn_impl)
            x = x + attn_out
            hh = L.apply_norm(cfg, lp["cross_norm"], x)
            enc_kv = L.encode_cross_kv(lp["cross"], cfg, enc_out)
            x = x + L.cross_attention_block(lp["cross"], cfg, hh, enc_kv)
            hh = L.apply_norm(cfg, lp["mlp_norm"], x)
            return x + L.mlp_block(lp["mlp"], cfg, hh), None

        h, _ = layer_scan(body, h, params["layers"])
        aux = jnp.float32(0)
    else:
        raise ValueError(cfg.family)

    return unembed(params, cfg, h), aux


def encode_audio(params: Params, cfg, frames: jnp.ndarray,
                 attn_impl: str = "xla", remat: bool = False) -> jnp.ndarray:
    """Whisper encoder over stub frame embeddings (B, n_frames, d)."""
    ckpt = (lambda f: jax.checkpoint(f, prevent_cse=False)) if remat else (
        lambda f: f)
    h = frames.astype(_dtype(cfg)) + params["enc_pos"][None, : frames.shape[1]]

    @ckpt
    def body(x, lp):
        hh = L.apply_norm(cfg, lp["attn_norm"], x)
        q = hh @ lp["attn"]["wq"]
        k = hh @ lp["attn"]["wk"]
        v = hh @ lp["attn"]["wv"]
        b, s, d = hh.shape
        nh, hd = cfg.n_heads, cfg.head_dim
        out = L.sdpa(
            q.reshape(b, s, nh, hd), k.reshape(b, s, cfg.n_kv_heads, hd),
            v.reshape(b, s, cfg.n_kv_heads, hd), causal=False,
        )
        x = x + out.reshape(b, s, nh * hd) @ lp["attn"]["wo"]
        hh = L.apply_norm(cfg, lp["mlp_norm"], x)
        return x + L.mlp_block(lp["mlp"], cfg, hh), None

    h, _ = layer_scan(body, h, params["enc_layers"])
    return L.apply_norm(cfg, params["enc_final_norm"], h)


# =========================================================================== #
# KV / state caches
# =========================================================================== #


def kv_buffer_len(cfg, max_len: int) -> int:
    """Physical KV buffer length: ring-bounded for SWA / sliding-window mode."""
    if cfg.attention_type == "swa":
        return min(max_len, cfg.swa_window)
    return max_len


def init_cache(cfg, batch: int, max_len: int, dtype=None,
               *, sliding_window: Optional[int] = None,
               kv_dtype: Optional[str] = None) -> Cache:
    """Build the decode cache.  ``sliding_window`` forces a ring buffer of the
    given size (the long_500k carve-in for full-attention archs).
    ``kv_dtype="int8"`` allocates a quantized cache (beyond-paper §Perf)."""
    dtype = dtype or _dtype(cfg)
    # per-slot lengths: decode slots advance independently (continuous batching)
    cache: Cache = {"len": jnp.zeros((batch,), jnp.int32)}
    kh, hd = cfg.n_kv_heads, cfg.head_dim

    def kv(n_sites, buf_len, ring):
        if kv_dtype == "int8":
            return KVCache(
                jnp.zeros((n_sites, batch, buf_len, kh, hd), jnp.int8),
                jnp.zeros((n_sites, batch, buf_len, kh, hd), jnp.int8),
                ring,
                jnp.zeros((n_sites, batch, buf_len), jnp.float32),
                jnp.zeros((n_sites, batch, buf_len), jnp.float32),
            )
        return KVCache(
            jnp.zeros((n_sites, batch, buf_len, kh, hd), dtype),
            jnp.zeros((n_sites, batch, buf_len, kh, hd), dtype),
            ring,
        )

    if cfg.family in ("dense", "moe", "vlm"):
        buf = kv_buffer_len(cfg, max_len)
        if sliding_window is not None:
            buf = min(buf, sliding_window)
        ring = buf < max_len
        cache["kv"] = kv(cfg.n_layers, buf, ring)
    elif cfg.family == "ssm":
        cache["ssm"] = jax.vmap(
            lambda _: S.init_ssm_state(cfg, batch, dtype)
        )(jnp.arange(cfg.n_layers))
    elif cfg.family == "hybrid":
        n_groups, inner, tail = hybrid_layout(cfg)
        cache["groups_ssm"] = jax.vmap(
            lambda _: jax.vmap(lambda __: S.init_ssm_state(cfg, batch, dtype))(
                jnp.arange(inner)
            )
        )(jnp.arange(n_groups))
        if tail:
            cache["tail_ssm"] = jax.vmap(
                lambda _: S.init_ssm_state(cfg, batch, dtype)
            )(jnp.arange(tail))
        buf = kv_buffer_len(cfg, max_len)
        ring = buf < max_len
        cache["kv"] = kv(n_groups, buf, ring)
    elif cfg.family == "audio":
        buf = min(max_len, cfg.max_position_embeddings)
        cache["kv"] = kv(cfg.n_layers, buf, False)
        # cross-attention K/V computed once at prefill
        nf = cfg.encoder.n_frames
        chd = cfg.d_model // cfg.n_heads
        cache["cross_kv"] = KVCache(
            jnp.zeros((cfg.n_layers, batch, nf, cfg.n_heads, chd), dtype),
            jnp.zeros((cfg.n_layers, batch, nf, cfg.n_heads, chd), dtype),
        )
    return cache


# =========================================================================== #
# Prefill
# =========================================================================== #


def prefill(params: Params, cfg, batch: Dict, cache: Cache,
            *, attn_impl: str = "xla", moe_impl: str = "dense",
            last_index: Optional[jnp.ndarray] = None):
    """Process the full prompt, fill the cache, return last-position logits.

    ``last_index`` (B,) selects the position whose logits are returned —
    engines right-pad prompts to buckets and need the *true* last position.

    The prefill-side Pallas kernels (flash_attention, ssd_scan) are
    single-device: a tensor-parallel caller passes ``attn_impl="xla"``
    (the engine does, and says so once — DESIGN.md §11).
    """
    h, pos = embed_inputs(params, cfg, batch)
    s = h.shape[1]
    cos_sin = L.positional_cos_sin(cfg, pos) if cfg.rope_type in ("rope", "mrope") else None

    if cfg.family in ("dense", "moe", "vlm"):
        kvc = cache["kv"]
        buf_len = kvc.k.shape[2]
        ring = kvc.ring
        quant = kvc.quantized

        def body(carry, inp):
            x, aux = carry
            if quant:
                lp, kb, vb, ksc, vsc = inp
            else:
                lp, kb, vb = inp
            x, (k, v), a = _dense_body(cfg, attn_impl, moe_impl, lp, x,
                                       cos_sin)
            if quant:
                k, ks = L.quantize_kv(k)
                v, vs = L.quantize_kv(v)
            if ring:
                # ring prefill: only the last `take` tokens fit the window;
                # write them at their absolute-position slots (pos % buf_len)
                take = min(s, buf_len)
                slots = (jnp.arange(s - take, s)) % buf_len
                kb = kb.at[:, slots].set(k[:, -take:])
                vb = vb.at[:, slots].set(v[:, -take:])
                if quant:
                    ksc = ksc.at[:, slots].set(ks[:, -take:])
                    vsc = vsc.at[:, slots].set(vs[:, -take:])
            else:
                kb = jax.lax.dynamic_update_slice(kb, k, (0, 0, 0, 0))
                vb = jax.lax.dynamic_update_slice(vb, v, (0, 0, 0, 0))
                if quant:
                    ksc = jax.lax.dynamic_update_slice(ksc, ks, (0, 0))
                    vsc = jax.lax.dynamic_update_slice(vsc, vs, (0, 0))
            if quant:
                return (x, aux + a), (kb, vb, ksc, vsc)
            return (x, aux + a), (kb, vb)

        if quant:
            (h, aux), (knew, vnew, ksnew, vsnew) = layer_scan(
                body, (h, jnp.float32(0)),
                (params["layers"], kvc.k, kvc.v, kvc.k_scale, kvc.v_scale),
            )
            cache = dict(cache)
            cache["kv"] = KVCache(knew, vnew, ring, ksnew, vsnew)
        else:
            (h, aux), (knew, vnew) = layer_scan(
                body, (h, jnp.float32(0)), (params["layers"], kvc.k, kvc.v)
            )
            cache = dict(cache)
            cache["kv"] = KVCache(knew, vnew, ring)
    elif cfg.family == "ssm":
        def body(x, lp):
            hh = L.apply_norm(cfg, lp["norm"], x)
            out, state = S.ssm_forward(lp["ssm"], cfg, hh, impl=attn_impl,
                                       return_state=True)
            return x + out, state

        h, states = layer_scan(body, h, params["layers"])
        cache = dict(cache)
        cache["ssm"] = states
        aux = jnp.float32(0)
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        kvc = cache["kv"]
        buf_len = kvc.k.shape[2]
        ring = kvc.ring

        def inner(x, lp):
            hh = L.apply_norm(cfg, lp["norm"], x)
            out, state = S.ssm_forward(lp["ssm"], cfg, hh, impl=attn_impl,
                                       return_state=True)
            return x + out, state

        def group(x, inp):
            gp, kb, vb = inp
            x, gstates = layer_scan(inner, x, gp)
            x, (k, v), _ = _dense_body(cfg, attn_impl, moe_impl, shared, x,
                                       cos_sin)
            if ring:
                take = min(s, buf_len)
                slots = (jnp.arange(s - take, s)) % buf_len
                kb = kb.at[:, slots].set(k[:, -take:])
                vb = vb.at[:, slots].set(v[:, -take:])
            else:
                kb = jax.lax.dynamic_update_slice(kb, k, (0, 0, 0, 0))
                vb = jax.lax.dynamic_update_slice(vb, v, (0, 0, 0, 0))
            return x, (gstates, kb, vb)

        h, (gstates, knew, vnew) = layer_scan(
            group, h, (params["groups"], kvc.k, kvc.v)
        )
        cache = dict(cache)
        cache["groups_ssm"] = gstates
        cache["kv"] = KVCache(knew, vnew, ring)
        if "tail" in params:
            h, tstates = layer_scan(inner, h, params["tail"])
            cache["tail_ssm"] = tstates
        aux = jnp.float32(0)
    elif cfg.family == "audio":
        enc_out = encode_audio(params, cfg, batch["frames"], attn_impl)
        kvc = cache["kv"]

        def body(x, inp):
            lp, kb, vb = inp
            hh = L.apply_norm(cfg, lp["attn_norm"], x)
            attn_out, (k, v) = L.attention_block(lp["attn"], cfg, hh, None,
                                                 attn_impl=attn_impl)
            x = x + attn_out
            kb = jax.lax.dynamic_update_slice(kb, k, (0, 0, 0, 0))
            vb = jax.lax.dynamic_update_slice(vb, v, (0, 0, 0, 0))
            hh = L.apply_norm(cfg, lp["cross_norm"], x)
            ck, cv = L.encode_cross_kv(lp["cross"], cfg, enc_out)
            x = x + L.cross_attention_block(lp["cross"], cfg, hh, (ck, cv))
            hh = L.apply_norm(cfg, lp["mlp_norm"], x)
            return x + L.mlp_block(lp["mlp"], cfg, hh), (kb, vb, ck, cv)

        h, (knew, vnew, ck, cv) = layer_scan(
            body, h, (params["layers"], kvc.k, kvc.v)
        )
        cache = dict(cache)
        cache["kv"] = KVCache(knew, vnew)
        cache["cross_kv"] = KVCache(ck, cv)
        aux = jnp.float32(0)
    cache["len"] = jnp.full((h.shape[0],), s, jnp.int32)
    if last_index is not None:
        hsel = h[jnp.arange(h.shape[0]), last_index][:, None, :]
    else:
        hsel = h[:, -1:, :]
    logits = unembed(params, cfg, hsel)
    return logits, cache


#: families :func:`prefill_chunk` supports — attention-only stacks whose KV
#: writes are position-addressable.  Recurrent state (ssm/hybrid) absorbs
#: every position it sees, and audio carries encoder cross-KV seeded by the
#: one-shot path; both keep exact one-shot prefill.
CHUNKABLE_FAMILIES = ("dense", "moe", "vlm")


def prefill_chunk(params: Params, cfg, batch: Dict, cache: Cache,
                  *, attn_impl: str = "xla", moe_impl: str = "dense",
                  start, valid_len):
    """Process ONE prompt chunk against a partially-filled cache.

    ``batch["tokens"]`` is (B, C) — C chunk tokens (right-padded to a shape
    bucket), of which the first ``valid_len`` (B,) are real, starting at
    absolute position ``start`` (B,) = tokens already prefilled.  The chunk's
    K/V are span-written into the cache at ``[start, start + valid_len)``
    and its queries attend over the whole buffer under a ``kv_len`` mask, so
    running a prompt as chunks is **bit-identical** to :func:`prefill` (see
    ``layers.attention_block``).  Returns logits at the chunk's last valid
    position (B, 1, V) — the caller samples the first output token from the
    final chunk's logits, exactly as it does from one-shot prefill's.

    Only :data:`CHUNKABLE_FAMILIES` with dense unquantized KV caches are
    supported; callers fall back to one-shot prefill otherwise.
    """
    if cfg.family not in CHUNKABLE_FAMILIES:
        raise ValueError(
            f"prefill_chunk supports families {CHUNKABLE_FAMILIES}, "
            f"got {cfg.family!r} — use one-shot prefill")
    tokens = batch["tokens"]
    b, c = tokens.shape
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
    valid = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (b,))
    pos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    h, pos = embed_inputs(params, cfg, {**batch, "positions": pos})
    cos_sin = (L.positional_cos_sin(cfg, pos)
               if cfg.rope_type in ("rope", "mrope") else None)
    kvc = cache["kv"]

    def body(carry, inp):
        x, aux = carry
        lp, kb, vb = inp
        x, nkv, a = _dense_body(cfg, attn_impl, moe_impl, lp, x, cos_sin,
                                cache=L.KVCache(kb, vb, kvc.ring),
                                cur_index=start, valid_len=valid)
        return (x, aux + a), (nkv.k, nkv.v)

    (h, _), (knew, vnew) = layer_scan(
        body, (h, jnp.float32(0)), (params["layers"], kvc.k, kvc.v)
    )
    new_cache = dict(cache)
    new_cache["kv"] = KVCache(knew, vnew, kvc.ring)
    new_cache["len"] = start + valid
    hsel = h[jnp.arange(b), valid - 1][:, None, :]
    return unembed(params, cfg, hsel), new_cache


# =========================================================================== #
# Decode step
# =========================================================================== #


def decode_step(params: Params, cfg, tokens: jnp.ndarray, cache: Cache,
                *, attn_impl: str = "xla", moe_impl: str = "grouped",
                active: Optional[jnp.ndarray] = None, mesh=None):
    """One-token auto-regressive step.  tokens (B, 1) -> (logits, cache).

    ``active`` (B,) bool — the continuous-batching mask: rows marked
    inactive (unoccupied slots, or slots frozen at EOS mid-window) are
    computed but their cache is left bit-identical — no KV/state write, no
    ``len`` advance — so a statically-shaped batch can carry dead slots
    through a shared dispatch without corrupting them.  ``attn_impl="pallas"``
    routes the attention read through the Pallas flash-decode kernel
    (:mod:`repro.kernels.decode_attention`) with the per-slot ``len`` vector
    as kv lengths; ``"xla"`` is the einsum reference path.

    ``mesh`` — when the caller runs under a TP mesh with head-sharded KV
    (``kv_shard="heads"``), passing the mesh routes the Pallas read through
    the ``shard_map``-wrapped kernel so each shard attends over its local
    heads (DESIGN.md §11).  Only valid for layouts where the head axes
    divide the ``"model"`` mesh axis — the engine gates this via
    :func:`repro.launch.partition.pallas_decode_support`.
    """
    b = tokens.shape[0]
    cur = jnp.broadcast_to(jnp.asarray(cache["len"]), (b,))  # per-slot lengths
    h = params["embed"][tokens]
    pos = cur[:, None]  # (B, 1)
    if cfg.rope_type == "learned":
        safe = jnp.minimum(cur, cfg.max_position_embeddings - 1)
        h = h + params["pos_embed"][safe][:, None, :]
    cos_sin = (
        L.positional_cos_sin(cfg, pos)
        if cfg.rope_type in ("rope", "mrope")
        else None
    )
    new_cache = dict(cache)

    if cfg.family in ("dense", "moe", "vlm"):
        kvc = cache["kv"]
        ring = kvc.ring
        quant = kvc.quantized

        def body(carry, inp):
            x, aux = carry
            if quant:
                lp, kb, vb, ksc, vsc = inp
                lc = KVCache(kb, vb, ring, ksc, vsc)
            else:
                lp, kb, vb = inp
                lc = KVCache(kb, vb, ring)
            x, nkv, a = _dense_body(cfg, attn_impl, moe_impl, lp, x, cos_sin,
                                    cache=lc, cur_index=cur, active=active,
                                    mesh=mesh)
            if quant:
                return (x, aux + a), (nkv.k, nkv.v, nkv.k_scale, nkv.v_scale)
            return (x, aux + a), (nkv.k, nkv.v)

        if quant:
            (h, _), (knew, vnew, ksnew, vsnew) = layer_scan(
                body, (h, jnp.float32(0)),
                (params["layers"], kvc.k, kvc.v, kvc.k_scale, kvc.v_scale),
            )
            new_cache["kv"] = KVCache(knew, vnew, ring, ksnew, vsnew)
        else:
            (h, _), (knew, vnew) = layer_scan(
                body, (h, jnp.float32(0)), (params["layers"], kvc.k, kvc.v)
            )
            new_cache["kv"] = KVCache(knew, vnew, ring)
    elif cfg.family == "ssm":
        def body(x, inp):
            lp, st = inp
            x, nst = _ssm_body(cfg, attn_impl, lp, x, state=st, active=active)
            return x, nst

        h, nstates = layer_scan(body, h, (params["layers"], cache["ssm"]))
        new_cache["ssm"] = nstates
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        kvc = cache["kv"]
        ring = kvc.ring

        def inner(x, inp):
            lp, st = inp
            x, nst = _ssm_body(cfg, attn_impl, lp, x, state=st, active=active)
            return x, nst

        def group(x, inp):
            gp, gst, kb, vb = inp
            x, ngst = layer_scan(inner, x, (gp, gst))
            x, nkv, _ = _dense_body(cfg, attn_impl, moe_impl, shared, x,
                                    cos_sin, cache=KVCache(kb, vb, ring),
                                    cur_index=cur, active=active, mesh=mesh)
            return x, (ngst, nkv.k, nkv.v)

        h, (ngroups, knew, vnew) = layer_scan(
            group, h,
            (params["groups"], cache["groups_ssm"], kvc.k, kvc.v),
        )
        new_cache["groups_ssm"] = ngroups
        new_cache["kv"] = KVCache(knew, vnew, ring)
        if "tail_ssm" in cache:
            h, ntail = layer_scan(inner, h,
                                    (params["tail"], cache["tail_ssm"]))
            new_cache["tail_ssm"] = ntail
    elif cfg.family == "audio":
        kvc = cache["kv"]
        cross = cache["cross_kv"]

        def body(x, inp):
            lp, kb, vb, ck, cv = inp
            hh = L.apply_norm(cfg, lp["attn_norm"], x)
            attn_out, nkv = L.attention_block(
                lp["attn"], cfg, hh, None, cache=KVCache(kb, vb),
                cur_index=cur, attn_impl=attn_impl, active=active, mesh=mesh,
            )
            x = x + attn_out
            hh = L.apply_norm(cfg, lp["cross_norm"], x)
            x = x + L.cross_attention_block(lp["cross"], cfg, hh, (ck, cv))
            hh = L.apply_norm(cfg, lp["mlp_norm"], x)
            return x + L.mlp_block(lp["mlp"], cfg, hh), (nkv.k, nkv.v)

        h, (knew, vnew) = layer_scan(
            body, h,
            (params["layers"], kvc.k, kvc.v, cross.k, cross.v),
        )
        new_cache["kv"] = KVCache(knew, vnew)
    else:
        raise ValueError(cfg.family)

    if active is not None:
        new_cache["len"] = jnp.where(active, cur + 1, cur)
    else:
        new_cache["len"] = cur + 1
    return unembed(params, cfg, h), new_cache


# =========================================================================== #
# Abstract params (for dry-run lowering without allocation)
# =========================================================================== #


def abstract_params(cfg) -> Params:
    return jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
    )


def abstract_cache(cfg, batch: int, max_len: int,
                   sliding_window: Optional[int] = None,
                   kv_dtype: Optional[str] = None) -> Cache:
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch, max_len,
                          sliding_window=sliding_window, kv_dtype=kv_dtype)
    )
