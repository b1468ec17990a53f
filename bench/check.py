"""The comparison that decides ``correct``.

For a served request, the reference runs once over the prompt and the
served tokens; at each position that produced a served token it reads how
far the served token's logit lies below the reference's best logit.  The
widest such gap over a sample of finished requests is compared with the
configuration's limit.  Valid for greedy decoding, which is what the
cells serve.

The control reads the same gap for the token that the reference computed
in fp8 puts first, at the same positions of the same sequences.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: padded sequence lengths the reference compiles for
LENGTH_BUCKETS = (256, 512, 1024, 2048, 4096, 8192)


@partial(jax.jit, static_argnames=("reference", "spec", "control"))
def _gaps(weights, tokens, targets, *, reference, spec, control: bool):
    with jax.default_matmul_precision("highest"):
        ref = reference.logits(weights, spec, tokens)
        if control:
            targets = jnp.argmax(
                reference.logits(weights, spec, tokens, "fp8"), -1)
    best = jnp.max(ref, axis=-1)
    chosen = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
    return best - chosen


def widest_gap(reference, weights, spec, prompt, served, *,
               control: bool = False) -> float:
    """Widest gap over the served tokens of one request (``control``: over
    the fp8 reference's first choices at the same positions)."""
    seq = list(prompt) + list(served[:-1])
    size = next(b for b in LENGTH_BUCKETS if b >= len(seq))
    tokens = np.zeros((size,), np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros((size,), np.int32)
    first = len(prompt) - 1
    targets[first:first + len(served)] = served
    gaps = _gaps(weights, tokens, targets, reference=reference, spec=spec,
                 control=control)
    return float(np.max(np.asarray(gaps)[first:first + len(served)]))


def sample(finished: dict, n: int, rng: np.random.RandomState) -> list:
    """``n`` request ids drawn from ``finished`` ({rid: served tokens}),
    always with the one that served the most tokens."""
    if not finished:
        return []
    ids = sorted(finished)
    longest = max(ids, key=lambda r: (len(finished[r]), r))
    rest = [r for r in ids if r != longest]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]
