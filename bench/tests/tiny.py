"""A tiny cell for CPU tests: the qwen2-1.5b layer shape (q/k/v bias, tied
embeddings) at two layers and 128 wide, a few slots, short traffic."""
from __future__ import annotations

import copy

from bench import registry

NAME = "qwen2-1.5b.chat-steady"


def cell(name: str = NAME, **traffic_over) -> registry.Cell:
    real = registry.cell(name)
    cfg = copy.deepcopy(real.cfg)
    cfg.update(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
               num_attention_heads=2, num_key_value_heads=1, head_dim=64,
               vocab_size=8192)
    cfg["serving"].update(slots=2, max_len=64, attn_impl="xla",
                          knee_rps=4.0)
    tr = copy.deepcopy(real.traffic)
    tr.update(warmup_s=0.5, trace_s=1.0, check_requests=3,
              drain_cap_s=min(tr["drain_cap_s"], 20.0))
    tr["prompt_tokens"].update(median=12, min=4, max=24)
    tr["output"]["cap"] = 24
    tr.update(traffic_over)
    return registry.Cell(name=name, cfg=cfg, traffic=tr,
                         end_to_end=real.end_to_end,
                         per_layer=real.per_layer)
