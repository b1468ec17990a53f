"""Pallas chunked-SSD scan kernel (Mamba2).

TPU adaptation of the SSD algorithm (arXiv:2405.21060): within a chunk the
recurrence is a pair of small dense matmuls (MXU work); across chunks a
(P × N) state is carried in VMEM scratch through the sequential trailing grid
axis — no CUDA selective-scan, no inter-block synchronisation.

  grid = (batch, heads, num_chunks)
  per step:  y_diag = (C B^T ∘ L) x        (intra-chunk, lower-triangular L)
             y_off  = exp(a_cum) · C h_in  (inter-chunk via carried state)
             h_out  = exp(a_cum[-1]) h_in + (decay ∘ B)^T x

Inputs are pre-scaled (x ← x·dt, a ← dt·A) as in the model layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: contraction dims for ``a @ b.T`` (NT) and ``a.T @ b`` (TN)
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _heads_major(t):
    """(B, S, H, ...) <-> (B, H, S, ...)."""
    return jnp.swapaxes(t, 1, 2)


def _ssd_kernel(
    x_ref, acum_ref, b_ref, c_ref, y_ref, fs_ref,
    state_ref,
    *,
    chunk: int,
    n_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)   # (C, P)
    a_cum = acum_ref[...]                # (C, 1) in-chunk cumsum of a
    bm = b_ref[...].astype(jnp.float32)  # (C, N)
    cm = c_ref[...].astype(jnp.float32)  # (C, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col
    # a_cum as a row vector, via the diagonal (no in-kernel transpose)
    a_cum_row = jnp.sum(jnp.where(row == col, a_cum, 0.0), axis=0,
                        keepdims=True)  # (1, C)
    # segsum: seg[t, s] = sum_{s < r <= t} a[r] for s <= t
    seg = a_cum - a_cum_row
    L = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)

    # intra-chunk
    scores = jax.lax.dot_general(cm, bm, _NT) * L   # (C, C)
    y = jnp.dot(scores, x)                          # (C, P)

    # inter-chunk
    h_in = state_ref[...]                           # (P, N)
    y += jnp.exp(a_cum) * jax.lax.dot_general(cm, h_in, _NT)

    # state carry
    total = a_cum[chunk - 1:, :]                    # (1, 1)
    decay_states = jnp.exp(total - a_cum)           # (C, 1)
    # exp(total) as a (1, N) row: Mosaic cannot broadcast a (1, 1) value
    # over sublanes and lanes at once
    n = h_in.shape[1]
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, n), 0) == chunk - 1
    total_row = jnp.sum(jnp.where(last, a_cum, 0.0), axis=0, keepdims=True)
    h_out = h_in * jnp.exp(total_row) + jax.lax.dot_general(
        x * decay_states, bm, _TN)                  # (P, N)
    state_ref[...] = h_out

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        fs_ref[...] = h_out.astype(fs_ref.dtype)


def ssd_scan(
    x: jnp.ndarray,   # (B, S, H, P) pre-multiplied by dt
    a: jnp.ndarray,   # (B, S, H)    log decay = dt * A
    Bm: jnp.ndarray,  # (B, S, H, N)
    Cm: jnp.ndarray,  # (B, S, H, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
):
    """Head-major kernel: inputs are transposed to ``(B, H, S, ·)`` so each
    block's trailing dims are ``(chunk, P|N)``, which Mosaic tiles for any
    head dim.  The in-chunk cumulative sum of ``a`` is taken here and
    enters as a ``(chunk, 1)`` column."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    a_cum = jnp.cumsum(
        _heads_major(a).astype(jnp.float32).reshape(b, h, nc, chunk), axis=-1
    ).reshape(b, h, s, 1)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)

    def seq_spec(width):
        return pl.BlockSpec((None, None, chunk, width),
                            lambda b_, h_, ci: (b_, h_, ci, 0))

    y, fs = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[seq_spec(p), seq_spec(1), seq_spec(n), seq_spec(n)],
        out_specs=[
            seq_spec(p),
            pl.BlockSpec((None, None, p, n), lambda b_, h_, ci: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(_heads_major(x), a_cum, _heads_major(Bm), _heads_major(Cm))
    return _heads_major(y), fs
