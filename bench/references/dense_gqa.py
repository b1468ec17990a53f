"""Plain float32 reference of the dense GQA decoder layer that both
configurations use (Qwen2 and Yi share the Llama layer shape).

It follows the published description (Hugging Face ``Qwen2ForCausalLM`` /
``LlamaForCausalLM``): token embedding; per layer RMSNorm, q/k/v
projections (with bias where the configuration has ``attention_bias``),
rotate-half RoPE, causal grouped-query softmax attention, output
projection, residual, RMSNorm, SwiGLU MLP, residual; a final RMSNorm and
the output head (the transposed embedding where the embeddings are tied).
It imports nothing of the program: it reads the weight pytree that
:mod:`bench.weights` makes from the seed (in the shapes listed here by
:func:`weight_shapes`) and the configuration file's numbers.

Every matmul runs in float32 under ``jax.default_matmul_precision
("highest")``; the bf16 weights are upcast inside the layer scan, one
layer at a time, so the reference fits beside the weights on one chip.

``precision="fp8"`` is the control: every linear projection (q, k, v, o,
gate, up, down and the head) takes float8 e4m3 inputs, weights scaled per
output column and activations per token, with float32 accumulation.
Attention scores, softmax and norms stay float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: largest finite float8 e4m3fn value
FP8_MAX = 448.0


@dataclass(frozen=True)
class Spec:
    """The numbers of a configuration file that the forward pass reads."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    rms_norm_eps: float
    rope_theta: float
    tie_word_embeddings: bool
    attention_bias: bool

    @classmethod
    def from_config(cls, cfg: dict) -> "Spec":
        heads = cfg["num_attention_heads"]
        return cls(
            n_heads=heads,
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
            attention_bias=bool(cfg["attention_bias"]),
        )


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    and return the dequantized float32 values."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * scale


def _linear(x, w, precision):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x = _fp8(x, axis=-1)   # per token
        w = _fp8(w, axis=0)    # per output column
    return x @ w


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotate-half RoPE. x (S, H, D), pos (S,)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(spec: Spec, precision: str, x, lp):
    s = x.shape[0]
    h, kh, d = spec.n_heads, spec.n_kv_heads, spec.head_dim
    pos = jnp.arange(s)
    a = lp["attn"]
    y = _rmsnorm(x, lp["attn_norm"]["scale"], spec.rms_norm_eps)
    q = _linear(y, a["wq"], precision)
    k = _linear(y, a["wk"], precision)
    v = _linear(y, a["wv"], precision)
    if spec.attention_bias:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q = _rope(q.reshape(s, h, d), pos, spec.rope_theta)
    k = _rope(k.reshape(s, kh, d), pos, spec.rope_theta)
    v = v.reshape(s, kh, d)
    q = q.reshape(s, kh, h // kh, d)
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) / np.sqrt(d)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("grqk,kgd->qgrd", probs, v).reshape(s, h * d)
    x = x + _linear(o, a["wo"], precision)
    m = lp["mlp"]
    y = _rmsnorm(x, lp["mlp_norm"]["scale"], spec.rms_norm_eps)
    act = jax.nn.silu(_linear(y, m["w_gate"], precision))
    x = x + _linear(act * _linear(y, m["w_up"], precision), m["w_down"],
                    precision)
    return x, None


def logits(weights, spec: Spec, tokens, precision: str = "float32"):
    """(S,) token ids -> (S, V) float32 logits of the next token."""
    x = weights["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(partial(_layer, spec, precision), x,
                        weights["layers"])
    x = _rmsnorm(x, weights["final_norm"]["scale"], spec.rms_norm_eps)
    head = (weights["embed"].T if spec.tie_word_embeddings
            else weights["lm_head"])
    return _linear(x, head, precision)


def weight_shapes(cfg: dict) -> dict:
    """{path: (shape, init std)} of every weight in the layout the
    program's dense decoder reads (stacked layers under ``layers``); a std
    of None is a norm scale of ones.  Matrices get 1/sqrt(fan_in), the
    embedding and the biases 0.02."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kh = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    ff = cfg["intermediate_size"]
    v = cfg["vocab_size"]
    n = cfg["num_hidden_layers"]
    out = {
        ("embed",): ((v, d), 0.02),
        ("final_norm", "scale"): ((d,), None),
        ("layers", "attn_norm", "scale"): ((n, d), None),
        ("layers", "mlp_norm", "scale"): ((n, d), None),
        ("layers", "attn", "wq"): ((n, d, h * hd), 1 / math.sqrt(d)),
        ("layers", "attn", "wk"): ((n, d, kh * hd), 1 / math.sqrt(d)),
        ("layers", "attn", "wv"): ((n, d, kh * hd), 1 / math.sqrt(d)),
        ("layers", "attn", "wo"): ((n, h * hd, d), 1 / math.sqrt(h * hd)),
        ("layers", "mlp", "w_gate"): ((n, d, ff), 1 / math.sqrt(d)),
        ("layers", "mlp", "w_up"): ((n, d, ff), 1 / math.sqrt(d)),
        ("layers", "mlp", "w_down"): ((n, ff, d), 1 / math.sqrt(ff)),
    }
    if not cfg["tie_word_embeddings"]:
        out[("lm_head",)] = ((d, v), 1 / math.sqrt(d))
    if cfg["attention_bias"]:
        out[("layers", "attn", "bq")] = ((n, h * hd), 0.02)
        out[("layers", "attn", "bk")] = ((n, kh * hd), 0.02)
        out[("layers", "attn", "bv")] = ((n, kh * hd), 0.02)
    return out


def program_kwargs(cfg: dict) -> dict:
    """The program's ``ModelConfig`` fields for this configuration (the
    harness builds the system under test from them)."""
    return dict(
        family="dense",
        n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim") or 0,
        qkv_bias=bool(cfg["attention_bias"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        dtype=cfg["torch_dtype"],
    )
