"""Seeded random weights for a configuration, made on the device in one
jitted call, in the dtype they are served in.

The shapes and spreads come from the configuration's reference module
(``weight_shapes``).  At those spreads (1/sqrt(fan_in) for matrices, 0.02
for the embedding) the residual stream keeps each token's identity through
the whole depth, so greedy decoding varies with the context and the top
two logits sit close enough that a lower precision changes the choice:
what the comparison with the reference needs.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("spec", "dtype"))
def _init(key, *, spec, dtype):
    tree: dict = {}
    keys = jax.random.split(key, len(spec))
    for k, (path, shape, std) in zip(keys, spec):
        if std is None:
            leaf = jnp.ones(shape, dtype)
        else:
            leaf = (jax.random.normal(k, shape, jnp.float32) * std
                    ).astype(dtype)
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return tree


def make(cfg: dict, reference, seed: int):
    """The weight pytree of ``cfg`` for ``seed``, on the default device."""
    spec = tuple((path, shape, std) for path, (shape, std)
                 in sorted(reference.weight_shapes(cfg).items()))
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 32)),
                             seed >> 32)
    return _init(key, spec=spec, dtype=cfg["torch_dtype"])
