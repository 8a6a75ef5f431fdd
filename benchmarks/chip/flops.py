"""Model FLOPs of one training step, from shapes alone.

What the model requires, not what the compiled program does: recompute
(remat) is not counted, nor the optimizer, norms, softmax or any other
elementwise work. The count is the usual 6·N·tokens for the matmul
parameters N (forward 2, backward 4), plus attention's score and value
products, 12 · layers · (heads · head_dim) · seq per token. Attention is
counted over the whole seq × seq score matrix, not halved for the causal
mask (the convention of PaLM's appendix B), since that is what a dense
kernel computes. The embedding lookup is a gather and counts nothing; the
LM head is a matmul and counts.
"""
from __future__ import annotations

from typing import Mapping


def matmul_params(cfg: Mapping) -> int:
    """Parameters that take part in a matmul per token: the attention and
    MLP projections of every layer and the LM head."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    f = cfg["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def train_step_flops(cfg: Mapping, batch: int, seq: int) -> float:
    """FLOPs one optimizer step needs over ``batch`` rows of ``seq``
    tokens (the global batch: every chip's rows together)."""
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    tokens = batch * seq
    dense = 6.0 * matmul_params(cfg) * tokens
    attn = 12.0 * cfg["num_hidden_layers"] * h * hd * seq * tokens
    return dense + attn
