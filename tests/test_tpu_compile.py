"""The Pallas kernels compile for a v5e chip at real widths.

Interpret mode (every other kernel test) never applies Mosaic's block and
memory rules; here each kernel is compiled by the TPU compiler for a
*described* v5e, with no chip attached, and must come out as a Mosaic
custom call.  Shapes: qwen2-1.5b attention (12 heads, 2 KV heads, head dim
128) and mamba2-130m's SSD scan (24 heads, head dim 64, state 128).  The
engine's whole decode window is compiled at qwen2-1.5b's serving shape, to
show that it reads the stacked KV cache in place.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every xdist worker imports this
file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import ssm_scan as ssd

BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32
#: qwen2-1.5b decode at a deployment's batch and context
B, H, KH, D, L = 32, 12, 2, 128, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _decode(q, k, v, kv_len, q_off):
    return dec.flash_decode(q, k, v, kv_len=kv_len, q_offset=q_off)


def _decode_int8(q, k, v, ks, vs, kv_len, q_off):
    return dec.flash_decode_int8(q, k, v, ks, vs, kv_len=kv_len,
                                 q_offset=q_off)


def _prefill(q, k, v):
    return fa.flash_attention(q, k, v, causal=True)


def _ssd(x, a, bm, cm):
    return ssd.ssd_scan(x, a, bm, cm, chunk=256)


CASES = {
    "flash_decode": (_decode, [((B, 1, H, D), BF16), ((B, L, KH, D), BF16),
                               ((B, L, KH, D), BF16), ((B,), I32),
                               ((B,), I32)]),
    "flash_decode_int8": (_decode_int8, [
        ((B, 1, H, D), BF16), ((B, L, KH, D), I8), ((B, L, KH, D), I8),
        ((B, L), F32), ((B, L), F32), ((B,), I32), ((B,), I32)]),
    "flash_attention": (_prefill, [((2, 2048, H, D), BF16),
                                   ((2, 2048, KH, D), BF16),
                                   ((2, 2048, KH, D), BF16)]),
    "ssd_scan": (_ssd, [((2, 1024, 24, 64), BF16), ((2, 1024, 24), F32),
                        ((2, 1024, 24, 128), BF16),
                        ((2, 1024, 24, 128), BF16)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, topo, no_persistent_cache):
    fn, shapes = CASES[name]
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_decode_compiles_for_two_v5e(topo, no_persistent_cache):
    """The TP=2 decode path: the kernel under ``shard_map`` over a
    described 2-chip mesh, KV heads split one per chip."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2,), ("model",), devices=topo.devices[:2])
    heads = NamedSharding(mesh, P(None, None, "model", None))
    repl = NamedSharding(mesh, P())
    fn = functools.partial(dec.flash_decode_sharded, mesh=mesh)
    compiled = jax.jit(
        lambda q, k, v, n, o: fn(q, k, v, kv_len=n, q_offset=o)).lower(
        jax.ShapeDtypeStruct((B, 1, H, D), BF16, sharding=heads),
        jax.ShapeDtypeStruct((B, L, KH, D), BF16, sharding=heads),
        jax.ShapeDtypeStruct((B, L, KH, D), BF16, sharding=heads),
        jax.ShapeDtypeStruct((B,), I32, sharding=repl),
        jax.ShapeDtypeStruct((B,), I32, sharding=repl)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" not in text and "all-gather" not in text


def test_decode_window_reads_the_stacked_cache_in_place(
        topo, no_persistent_cache, monkeypatch):
    """The engine's decode window at qwen2-1.5b's serving shape (28
    layers, 32 slots x 2048, pallas, K=2): the layer loop carries the
    stacked KV cache, so no instruction makes a layer's K/V buffer and no
    dynamic-update-slice writes a layer back; the kernel reads the stack."""
    from repro.configs import get_config
    from repro.engine.engine import EngineConfig, make_decode_window
    from repro.kernels import ops as kops
    from repro.models import transformer as T

    monkeypatch.setattr(kops, "_interpret", lambda: False)  # Mosaic
    cfg = get_config("qwen2-1.5b")
    slots, max_len = 32, 2048
    one_chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    program = make_decode_window(cfg, EngineConfig(
        max_slots=slots, max_len=max_len, attn_impl="pallas"), 2)
    compiled = jax.jit(program).lower(
        on_chip(T.abstract_params(cfg)),
        on_chip(T.abstract_cache(cfg, slots, max_len)),
        on_chip(jax.ShapeDtypeStruct((slots, 1), I32)),
        on_chip(jax.ShapeDtypeStruct((slots,), jnp.bool_)),
        on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))).compile()
    lines = compiled.as_text().splitlines()
    layer_kv = re.compile(r"\s*(ROOT )?%\S+ = bf16\[32,2048,2,128\]\{[^}]*\} "
                          r"(?!parameter|tuple|get-tuple-element)[\w-]+\(")
    made = [ln.strip()[:120] for ln in lines if layer_kv.match(ln)]
    assert not made, made
    stacked = "bf16[28,32,2048,2,128]"
    written_back = [ln.strip()[:120] for ln in lines
                    if f"= {stacked}" in ln and "dynamic-update-slice(" in ln]
    assert not written_back, written_back
    assert any(re.match(r"\s*%flash_decode\.\d+ = .*tpu_custom_call", ln)
               for ln in lines)
    # carrying the cache costs no temporaries: 132,798,464 B at this shape
    assert compiled.memory_analysis().temp_size_in_bytes <= 132_798_464
