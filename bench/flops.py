"""Operations and bytes a dense GQA decoder needs, counted from shapes.

Only useful work counts: tokens of real requests at their real positions,
never bucket padding, masked cache positions or frozen slots.
"""
from __future__ import annotations

from typing import Iterable


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kh = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, kh, hd, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through: the projections of every
    layer and the output head (the embedding lookup multiplies nothing)."""
    d, h, kh, hd, ff, v, n = _dims(cfg)
    layer = d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * ff
    return n * layer + d * v


def token_flops(cfg: dict, context: int) -> int:
    """FLOPs of one token that attends ``context`` positions (itself
    included): 2 per weight, plus QK^T and PV over the context in every
    layer."""
    d, h, kh, hd, ff, v, n = _dims(cfg)
    return 2 * matmul_params(cfg) + n * 4 * h * hd * context


def prefill_flops(cfg: dict, length: int) -> int:
    """FLOPs of a prefill of ``length`` tokens (token p attends p + 1)."""
    d, h, kh, hd, ff, v, n = _dims(cfg)
    return (length * 2 * matmul_params(cfg)
            + n * 4 * h * hd * length * (length + 1) // 2)


#: bytes of one cached or activation element (the cells serve bf16)
ITEMSIZE = 2


def decode_attention(cfg: dict, kv_lens: Iterable[int]):
    """(flops, bytes) one layer's decode-attention kernel call needs for
    rows attending ``kv_lens`` positions: q and out, and the K and V
    entries inside each row's length."""
    d, h, kh, hd, ff, v, n = _dims(cfg)
    flops = nbytes = 0
    for L in kv_lens:
        flops += 4 * h * hd * L
        nbytes += (2 * h * hd + 2 * kh * hd * L) * ITEMSIZE
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline bound: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
