"""Pallas flash-attention (prefill/training) kernel for TPU.

Blocked online-softmax attention with explicit VMEM tiling:
  grid = (batch, heads, num_q_blocks, num_kv_blocks) — the trailing KV axis
  iterates sequentially on TPU, so the running (m, l, acc) statistics live in
  VMEM scratch and persist across KV steps (the canonical Mosaic pattern).

Supports GQA (kv-head index derived statically from the query head), causal
masking with a query offset, and sliding-window (SWA) masking.  Block sizes
default to 128×128 — MXU-aligned on the (sublane, lane) = (8, 128) layout.
Q/K/V are viewed (free reshape) as ``(B, S, heads*D)`` and one head's
``(block, D)`` tile is selected by a block index on the fused last axis, so
the blocks satisfy Mosaic's tiling rule whenever ``D`` is a multiple of 128.

Validated on CPU in ``interpret=True`` mode against ``ref.reference_attention``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
    causal: bool,
    window: Optional[int],
    q_offset: int,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)  # (BQ, D)
    k = k_ref[...].astype(jnp.float32)  # (BK, D)
    v = v_ref[...].astype(jnp.float32)  # (BK, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (BQ, BK)

    qi = pl.program_id(2)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > (q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]  # (BQ, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v)
    m_ref[...] = m_cur

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Skv, KH, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    assert h % kh == 0
    rep = h // kh
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)
    n_q = sq // block_q
    n_k = skv // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=n_k,
        causal=causal,
        window=window,
        q_offset=q_offset,
    )
    grid = (b, h, n_q, n_k)
    q_spec = pl.BlockSpec((None, block_q, d), lambda b_, h_, qi, ki: (b_, qi, h_))
    kv_spec = pl.BlockSpec((None, block_k, d),
                           lambda b_, h_, qi, ki: (b_, ki, h_ // rep))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, sq, h * d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q.reshape(b, sq, h * d), k.reshape(b, skv, kh * d),
      v.reshape(b, skv, kh * d))
    return out.reshape(b, sq, h, d)
