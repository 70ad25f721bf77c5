"""Model FLOPs of a dense decoder layer stack, from a configuration's shapes.

Counts what the forward and backward passes require, not what the program
runs: no rematerialised forward, and attention over the whole square of
scores (the step computes dense attention without skipping the masked half,
so the non-causal count is the work the step must do).

Per token: 6 * N_matmul for the weight matmuls (2 forward, 4 backward) plus
12 * L * d * S for the score and value products of attention (2 * 2 * S * d
forward per layer, three times that with the backward). The embedding lookup
is a gather and counts nothing.
"""
from __future__ import annotations

from typing import Mapping


def matmul_params(model: Mapping[str, int]) -> int:
    """Parameters that take part in a matmul per token: q, k, v, o, the
    SwiGLU gate, up and down projections of every layer, and the head."""
    d = model["hidden_size"]
    h, g = model["num_attention_heads"], model["num_key_value_heads"]
    dh = model["head_dim"]
    f = model["intermediate_size"]
    per_layer = d * h * dh * 2 + d * g * dh * 2 + 3 * d * f
    return model["num_hidden_layers"] * per_layer + d * model["vocab_size"]


def flops_per_token(model: Mapping[str, int], seq_len: int) -> float:
    """Model FLOPs per trained token at sequence length ``seq_len``."""
    attn = 12 * model["num_hidden_layers"] * model["num_attention_heads"] \
        * model["head_dim"] * seq_len
    return 6.0 * matmul_params(model) + attn


def flops_per_step(model: Mapping[str, int], global_batch: int,
                   seq_len: int) -> float:
    return flops_per_token(model, seq_len) * global_batch * seq_len
