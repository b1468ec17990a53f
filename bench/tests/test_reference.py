"""The plain reference agrees with the program's own forward pass in
float32 at a small size, and the comparison reads 0 on the reference's own
greedy tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, registry, weights
from bench.tests import tiny


@pytest.mark.parametrize("name", ["qwen2-1.5b.chat-steady",
                                  "yi-6b-16l.chat-steady"])
def test_reference_matches_program_forward(name):
    from repro.configs.base import ModelConfig
    from repro.models import transformer as T

    cfg = dict(tiny.cell(name).cfg, torch_dtype="float32")
    ref = registry.reference(cfg["reference"])
    w = weights.make(cfg, ref, seed=3)
    tokens = np.random.RandomState(0).randint(8, 8192, size=40)
    mc = ModelConfig(arch_id="tiny", **ref.program_kwargs(cfg))
    with jax.default_matmul_precision("highest"):
        prog, _ = T.forward(w, mc, {"tokens": jnp.asarray(tokens[None])})
        mine = ref.logits(w, ref.Spec.from_config(cfg), jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(mine), np.asarray(prog[0]),
                               atol=2e-4, rtol=2e-4)


def test_greedy_reference_tokens_read_zero():
    cfg = dict(tiny.cell().cfg, torch_dtype="float32")
    ref = registry.reference(cfg["reference"])
    spec = ref.Spec.from_config(cfg)
    w = weights.make(cfg, ref, seed=4)
    prompt = list(range(20, 36))
    seq = list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(6):
            lg = ref.logits(w, spec, jnp.asarray(seq))
            seq.append(int(jnp.argmax(lg[-1])))
    served = seq[len(prompt):]
    assert check.widest_gap(ref, w, spec, prompt, served) == 0.0
    bad = [(t + 1) % cfg["vocab_size"] for t in served]
    assert check.widest_gap(ref, w, spec, prompt, bad) > 0.0
