"""Model FLOPs of one training step of a dense decoder, from its shapes.

Counted: 6 FLOPs per token for every matmul parameter (2 forward, 4
backward), the LM head included and the embedding lookup excluded, plus
causal self-attention, 6 x seq x n_heads x head_dim per token and layer
(QK^T and PV at 2 x seq/2 x n_heads x head_dim each forward, times 3 for
forward and backward). Recomputation (remat) and the masked half of a
causal score matrix that an implementation may compute are not counted:
this is the work the step requires, not the work a program happens to do.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul, per the configuration file's
    keys (HF names): attention projections, SwiGLU MLP and the LM head."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    f = cfg["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def flops_per_token(cfg: dict, seq_len: int) -> float:
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    attn = 6 * cfg["num_hidden_layers"] * seq_len * h * hd
    return 6.0 * matmul_params(cfg) + attn


def flops_per_step(cfg: dict, global_batch: int, seq_len: int) -> float:
    return flops_per_token(cfg, seq_len) * global_batch * seq_len
