"""Pallas flash-decode kernel: single-token attention over a long KV cache.

The decode-phase hot spot: one query token per sequence attending to a KV
cache of up to 512k entries.  The kernel blocks over the KV axis
(grid = (batch, num_kv_blocks), trailing axis sequential) with online
softmax statistics in VMEM scratch — the TPU analogue of flash-decoding's
split-K, with the partial-reduction carried through sequential grid steps
instead of an inter-SM reduction pass.

Layout: each step loads a ``(block_k, KH, D)`` tile holding every KV head
(the block's trailing dims are then full array dims, which Mosaic's
(8, 128) tiling rule accepts, and the cache is read in place with no
relayout), plus all ``H`` query heads.  Each KV head's ``rep = H // KH``
query heads attend it as one ``(rep, D)`` tile, so a KV block is read once
per group rather than once per query head.

Per-sequence dynamic state (valid cache length, absolute query position)
arrives via scalar prefetch (SMEM) so slots at different generation depths
batch together — exactly what ELIS's continuous batching produces.  The
int8-KV variant runs the same body with per-token fp32 scales applied to
the scores and probabilities, so HBM traffic is the int8 bytes.

Under a tensor-parallel mesh, :func:`flash_decode_sharded` runs the same
kernel per shard via ``shard_map`` over the TP axis: every (batch, head)
pair is independent (the online-softmax state is per-head), so splitting
the Q/KV head axes across devices needs no cross-device collective and is
**bit-identical** to the single-device kernel.  See ``docs/kernels.md``
for the full contract.
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

#: contract the last axis of both operands: ``a @ b.T`` without a transpose
_NT = (((1,), (1,)), ((), ()))


def _decode_kernel(
    kv_len_ref, q_off_ref,  # scalar-prefetch (SMEM): (B,) each
    q_ref, k_ref, v_ref, *rest,
    scale: float,
    block_k: int,
    n_kv_blocks: int,
    window: Optional[int],
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    n_groups, rep = m_ref.shape[0], m_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = kv_len_ref[bi]
    q_pos = q_off_ref[bi]
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    mask = (k_pos < kv_len) & (k_pos <= q_pos)
    if window is not None:
        mask &= k_pos > (q_pos - window)

    for g in range(n_groups):
        heads = slice(g * rep, (g + 1) * rep)
        q = q_ref[0, heads, :].astype(jnp.float32)  # (rep, D)
        k = k_ref[:, g, :].astype(jnp.float32)      # (BK, D)
        v = v_ref[:, g, :].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, _NT) * scale  # (rep, BK)
        if quantized:
            s = s * ks_ref[...]  # per-token K scale, (1, BK)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[g]  # (rep, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * vs_ref[...]  # per-token V scale folds into the weights
        acc_ref[g] = acc_ref[g] * alpha + jnp.dot(p, v)
        m_ref[g] = m_cur

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        for g in range(n_groups):
            denom = jnp.maximum(l_ref[g], 1e-30)
            o_ref[0, g * rep:(g + 1) * rep, :] = (
                acc_ref[g] / denom).astype(o_ref.dtype)


def _flash_decode(q, k, v, scales, *, kv_len, q_offset, window, block_k,
                  interpret):
    b, sq, h, d = q.shape
    assert sq == 1
    L, kh = k.shape[1], k.shape[2]
    rep = h // kh
    block_k = min(block_k, L)
    assert L % block_k == 0, (L, block_k)
    n_k = L // block_k
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    q_offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))

    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(d), block_k=block_k,
        n_kv_blocks=n_k, window=window, quantized=scales is not None)
    q_spec = pl.BlockSpec((None, 1, h, d), lambda b_, ki, *_: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec((None, block_k, kh, d),
                           lambda b_, ki, *_: (b_, ki, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q, k, v]
    if scales is not None:
        in_specs += [pl.BlockSpec((None, 1, block_k),
                                  lambda b_, ki, *_: (b_, 0, ki))] * 2
        operands += [s.astype(jnp.float32).reshape(b, 1, L) for s in scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_k),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((kh, rep, d), jnp.float32),
            pltpu.VMEM((kh, rep, 1), jnp.float32),
            pltpu.VMEM((kh, rep, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h, d), q.dtype),
        interpret=interpret,
    )(kv_len, q_offset, *operands)


def flash_decode_int8(
    q: jnp.ndarray,        # (B, 1, H, D)
    k: jnp.ndarray,        # (B, L, KH, D) int8
    v: jnp.ndarray,        # int8
    k_scale: jnp.ndarray,  # (B, L) fp32
    v_scale: jnp.ndarray,
    *,
    kv_len: jnp.ndarray,
    q_offset: jnp.ndarray,
    window: Optional[int] = None,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """int8-KV decode: K/V blocks arrive quantized with per-token fp32
    scales (the §Perf serving recipe)."""
    assert k.dtype == jnp.int8
    return _flash_decode(q, k, v, (k_scale, v_scale), kv_len=kv_len,
                         q_offset=q_offset, window=window, block_k=block_k,
                         interpret=interpret)


def flash_decode(
    q: jnp.ndarray,  # (B, 1, H, D)
    k: jnp.ndarray,  # (B, L, KH, D)
    v: jnp.ndarray,
    *,
    kv_len: jnp.ndarray,  # (B,) or scalar
    q_offset: jnp.ndarray,  # (B,) or scalar
    window: Optional[int] = None,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    return _flash_decode(q, k, v, None, kv_len=kv_len, q_offset=q_offset,
                         window=window, block_k=block_k, interpret=interpret)


def flash_decode_sharded(
    q: jnp.ndarray,  # (B, 1, H, D), heads sharded on ``axis``
    k: jnp.ndarray,  # (B, L, KH, D), kv heads sharded on ``axis``
    v: jnp.ndarray,
    *,
    kv_len: jnp.ndarray,    # (B,) or scalar, replicated
    q_offset: jnp.ndarray,  # (B,) or scalar, replicated
    mesh,
    axis: str = "model",
    window: Optional[int] = None,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """:func:`flash_decode` under a tensor-parallel mesh.

    Wraps the kernel in ``shard_map`` over the ``axis`` mesh axis with the
    Q and KV head axes partitioned (the ``kv_shard="heads"`` slot-cache
    layout) and the slot/batch axis plus the per-slot ``kv_len`` /
    ``q_offset`` vectors replicated.  Each shard attends over its local
    KV heads only; since every (batch, head) cell of the kernel grid is
    independent, no collective runs inside the kernel and the stitched
    output is bit-identical to the single-device kernel.

    Requires both head axes divisible by the mesh-axis size so each shard
    holds whole heads at the same GQA ratio (``H/tp ÷ KH/tp = H ÷ KH``);
    indivisible layouts (KV replicated by ``sanitize_specs``) must stay on
    the XLA path — the per-shard kernel would index the wrong KV head.
    """
    from jax.sharding import PartitionSpec as P

    b, sq, h, d = q.shape
    kh = k.shape[2]
    tp = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis])
    if h % tp or kh % tp:
        raise ValueError(
            f"flash_decode_sharded: heads ({h} q / {kh} kv) must divide the "
            f"'{axis}' mesh axis of size {tp} — this layout replicates KV "
            "and must use the XLA decode path")
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    q_offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))

    def local(q_, k_, v_, kv_len_, q_offset_):
        return flash_decode(q_, k_, v_, kv_len=kv_len_, q_offset=q_offset_,
                            window=window, block_k=block_k,
                            interpret=interpret)

    head_spec = P(None, None, axis, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(head_spec, head_spec, head_spec, P(None), P(None)),
        out_specs=head_spec,
        # pallas_call carries no varying-axis rule; the output really is
        # head-sharded, so skipping the check is sound here
        check_vma=False,
    )(q, k, v, kv_len, q_offset)
