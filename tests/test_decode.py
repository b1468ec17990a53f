"""Decode-path exactness: prefill + decode_step must reproduce forward()."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_archs
from repro.models import decode_step, forward, init_cache, init_params, prefill

TOL = 5e-5


def _batch(cfg, key, B, S):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["embeds"] = jax.random.normal(key, (B, cfg.frontend_tokens,
                                                  cfg.d_model))
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(key, (B, cfg.encoder.n_frames,
                                                  cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", sorted(list_archs()))
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    batch = _batch(cfg, jax.random.PRNGKey(1), B, S)
    cache = init_cache(cfg, B, 64)
    lg, cache = prefill(params, cfg, batch, cache)
    full, _ = forward(params, cfg, batch)
    assert float(jnp.max(jnp.abs(lg[:, -1] - full[:, -1]))) < TOL

    toks = batch["tokens"]
    nxt = jax.random.randint(jax.random.PRNGKey(2), (B, 3), 0, cfg.vocab_size)
    for i in range(3):
        lgd, cache = decode_step(params, cfg, nxt[:, i : i + 1], cache)
        ext = dict(batch)
        ext["tokens"] = jnp.concatenate([toks, nxt[:, : i + 1]], 1)
        lge, _ = forward(params, cfg, ext)
        err = float(jnp.max(jnp.abs(lgd[:, 0] - lge[:, -1])))
        assert err < TOL, (arch, i, err)


def test_heterogeneous_slot_lengths():
    """Continuous batching: slots at different depths decode identically to
    isolated per-slot decoding."""
    cfg = get_config("llama3.2-3b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    t0 = jax.random.randint(jax.random.PRNGKey(5), (1, 8), 0, cfg.vocab_size)
    t1 = jax.random.randint(jax.random.PRNGKey(6), (1, 12), 0, cfg.vocab_size)
    c0 = init_cache(cfg, 1, 64)
    c1 = init_cache(cfg, 1, 64)
    _, c0 = prefill(params, cfg, {"tokens": t0}, c0)
    _, c1 = prefill(params, cfg, {"tokens": t1}, c1)
    merged = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], axis=1)
        if a.ndim > 1 else jnp.concatenate([a, b]),
        c0, c1,
    )
    nxt = jnp.array([[3], [7]], jnp.int32)
    lgm, _ = decode_step(params, cfg, nxt, merged)
    l0, _ = decode_step(params, cfg, nxt[:1], c0)
    l1, _ = decode_step(params, cfg, nxt[1:], c1)
    assert float(jnp.max(jnp.abs(lgm[0] - l0[0]))) < TOL
    assert float(jnp.max(jnp.abs(lgm[1] - l1[0]))) < TOL


def test_ring_buffer_equals_full_within_window():
    """With a ring buffer >= attention window, sliding-window decode must be
    bit-equal to the full-cache SWA decode."""
    cfg = get_config("mixtral-8x7b").reduced()  # swa_window=64 (reduced)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 1, 8
    batch = _batch(cfg, jax.random.PRNGKey(1), B, S)
    full = init_cache(cfg, B, 256)        # swa -> buffer = window = 64 < 256
    assert full["kv"].k.shape[2] == cfg.swa_window
    _, full = prefill(params, cfg, batch, full)
    big = init_cache(cfg, B, 32)          # buffer 32 >= any reachable len
    assert not big["kv"].ring
    _, big = prefill(params, cfg, batch, big)
    nxt = jax.random.randint(jax.random.PRNGKey(2), (B, 5), 0, cfg.vocab_size)
    for i in range(5):
        lr, full = decode_step(params, cfg, nxt[:, i : i + 1], full)
        lf, big = decode_step(params, cfg, nxt[:, i : i + 1], big)
        assert float(jnp.max(jnp.abs(lr - lf))) < TOL, i


def test_long_context_ring_decode_stays_finite():
    """Ring decode far past the window: no NaNs, mask arithmetic holds."""
    cfg = get_config("llama3.2-3b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, 1, 10_000, sliding_window=8)
    assert cache["kv"].ring
    step = jax.jit(lambda t, c: decode_step(params, cfg, t, c))
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 40), 0,
                              cfg.vocab_size)
    for i in range(40):
        lg, cache = step(toks[:, i : i + 1], cache)
        assert not jnp.any(jnp.isnan(lg))
    assert int(cache["len"][0]) == 40


def _decode_step_sliced(params, cfg, tokens, cache, attn_impl, active):
    """Reference layer loop: each layer's K/V is a scan input, sliced out
    of the stacked cache, and a scan output, stacked back."""
    from repro.models import layers as L
    from repro.models import transformer as T

    cur = cache["len"]
    cos_sin = L.positional_cos_sin(cfg, cur[:, None])

    def body(x, inp):
        lp, layer_kv = inp
        x, kv, _ = T._dense_body(cfg, attn_impl, "grouped", lp, x, cos_sin,
                                 cache=layer_kv, cur_index=cur,
                                 active=active)
        return x, kv

    h, kv = jax.lax.scan(body, params["embed"][tokens],
                         (params["layers"], cache["kv"]))
    return T.unembed(params, cfg, h), {
        "len": jnp.where(active, cur + 1, cur), "kv": kv}


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("kind", ["dense", "ring", "int8"])
def test_decode_carried_cache_matches_sliced_layers(attn_impl, kind):
    """The layer loop carries the stacked cache and writes each row in
    place at its layer index; every read (kernel, XLA, ring, int8) gives
    bitwise the logits and cache of the loop that slices each layer out
    and stacks it back, and a frozen row's bytes stay untouched."""
    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    B = 3
    cache = init_cache(cfg, B, 32, sliding_window=16 if kind == "ring"
                       else None,
                       kv_dtype="int8" if kind == "int8" else None)
    assert cache["kv"].ring == (kind == "ring")
    batch = _batch(cfg, jax.random.PRNGKey(1), B, 12)
    _, cache = prefill(params, cfg, batch, cache)
    active = jnp.array([True, False, True])
    nxt = jax.random.randint(jax.random.PRNGKey(2), (B, 6), 0, cfg.vocab_size)
    got = want = cache
    carried = jax.jit(lambda t, c: decode_step(
        params, cfg, t, c, attn_impl=attn_impl, active=active))
    sliced = jax.jit(lambda t, c: _decode_step_sliced(
        params, cfg, t, c, attn_impl, active))
    for i in range(6):  # the ring wraps at position 16
        lg, got = carried(nxt[:, i:i + 1], got)
        lw, want = sliced(nxt[:, i:i + 1], want)
        assert jnp.array_equal(lg, lw), i
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert jnp.array_equal(a, b)
    for a, b in zip(jax.tree_util.tree_leaves(got["kv"]),
                    jax.tree_util.tree_leaves(cache["kv"])):
        assert jnp.array_equal(a[:, 1], b[:, 1])  # frozen row, every layer
