"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in ``interpret=True`` mode —
the kernel body runs in Python/XLA for correctness validation.  On TPU they
compile through Mosaic.  ``interpret`` is auto-detected from the backend.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ssm_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("window", "block_k", "mesh",
                                             "shard_axis"))
def flash_decode(q, k, v, *, kv_len, q_offset,
                 window: Optional[int] = None, block_k: int = 128,
                 mesh=None, shard_axis: str = "model", layer=0):
    """Flash-decode dispatch.  ``k``/``v`` are one layer's ``(B, L, KH,
    D)`` buffers or the stacked ``(n_layers, B, L, KH, D)`` cache, read
    in place at ``layer``.  With ``mesh`` (a static arg, so single- and
    multi-device callers never share a stale trace) the kernel runs
    ``shard_map``-ped over ``shard_axis`` with Q/KV heads partitioned —
    bit-identical per head to the single-device kernel."""
    if mesh is not None:
        return _dec.flash_decode_sharded(
            q, k, v, kv_len=kv_len, q_offset=q_offset, mesh=mesh,
            axis=shard_axis, window=window, block_k=block_k,
            interpret=_interpret(), layer=layer,
        )
    return _dec.flash_decode(
        q, k, v, kv_len=kv_len, q_offset=q_offset, window=window,
        block_k=block_k, interpret=_interpret(), layer=layer,
    )


@functools.partial(jax.jit, static_argnames=("window", "block_k"))
def flash_decode_int8(q, k, v, k_scale, v_scale, *, kv_len, q_offset,
                      window: Optional[int] = None, block_k: int = 128):
    """Decode attention over an int8-quantized KV cache (the §Perf serving
    recipe): HBM reads are int8, dequantization fuses into the block load."""
    return _dec.flash_decode_int8(
        q, k, v, k_scale, v_scale, kv_len=kv_len, q_offset=q_offset,
        window=window, block_k=block_k, interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, a, Bm, Cm, *, chunk: int = 256):
    return _ssd.ssd_scan(x, a, Bm, Cm, chunk=chunk, interpret=_interpret())
