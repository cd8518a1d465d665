"""Operations and bytes of a decoder's serving work, from the configuration
file's sizes alone (no program code).

Model FLOPs count the matrix products that a token needs: the linear layers
(2 FLOPs per multiply-add), attention over its context (QK^T and PV, 4 FLOPs
per head dimension per context position), and the LM head for each token
whose logits are used (the last prompt position, and every decoded token).
K/V regenerated from activation checkpoints is recompute: ``regen_flops``
counts it apart, and the model FLOPs leave it out.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2


def _dims(c: Dict):
    return (c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_rows"])


def layer_params(c: Dict) -> int:
    """Weights of one layer (norm gains and biases included)."""
    L, d, H, KVH, D, F, V = _dims(c)
    attn = d * H * D + 2 * d * KVH * D + H * D * d
    ffn = (3 if c["ffn"].startswith("gated") else 2) * d * F
    norms = 2 * d * (2 if c["norm"] == "layernorm" else 1)
    return attn + ffn + norms


def layer_bytes(c: Dict) -> int:
    return layer_params(c) * BF16


def head_params(c: Dict) -> int:
    """The LM head's weights (the tied embedding where it is tied)."""
    return c["hidden_size"] * c["vocab_rows"]


def kv_bytes_per_token(c: Dict) -> int:
    return c["num_hidden_layers"] * 2 * c["num_key_value_heads"] * \
        c["head_dim"] * BF16


def act_bytes_per_token(c: Dict) -> int:
    return c["num_hidden_layers"] * c["hidden_size"] * BF16


def _linear_flops(c: Dict) -> int:
    L = c["num_hidden_layers"]
    return 2 * L * (layer_params(c) - 2 * c["hidden_size"] *
                    (2 if c["norm"] == "layernorm" else 1))


def _attn_flops(c: Dict, ctx: float) -> float:
    return 4.0 * c["num_hidden_layers"] * c["num_attention_heads"] * \
        c["head_dim"] * ctx


def prefill_flops(c: Dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens: every position's layers, causal
    attention, and the LM head at the last position."""
    return (prompt * _linear_flops(c)
            + _attn_flops(c, prompt * (prompt + 1) / 2)
            + 2.0 * head_params(c))


def decode_flops(c: Dict, context: int) -> float:
    """One decoded token that attends to ``context`` earlier positions and
    itself."""
    return _linear_flops(c) + _attn_flops(c, context + 1) + \
        2.0 * head_params(c)


def regen_flops(c: Dict, act_tokens: int) -> float:
    """K/V regenerated from ``act_tokens`` checkpoints in every layer."""
    return 2.0 * c["num_hidden_layers"] * act_tokens * c["hidden_size"] * \
        2 * c["num_key_value_heads"] * c["head_dim"]


def decode_step_bytes(c: Dict, kv_tokens: int, act_tokens: int,
                      slots: int) -> float:
    """Bytes one decode step must read: every layer's weights, the LM head,
    each slot's KV rows and ACT rows, and the new rows it writes."""
    return (c["num_hidden_layers"] * layer_bytes(c) + head_params(c) * BF16
            + kv_tokens * kv_bytes_per_token(c)
            + act_tokens * act_bytes_per_token(c)
            + slots * kv_bytes_per_token(c))
