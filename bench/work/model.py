"""Model operations per token of a dense decoder, from its config file.

``2 x`` the matmul parameters (projections, FFN, logits head) plus
attention's ``4 * head_dim * context * heads`` per layer. Embedding
lookups, norms and softmax are not counted.
"""

from __future__ import annotations


def matmul_params(c: dict) -> int:
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // h
    ffn = c["intermediate_size"]
    n_ffn = 3 if c["hidden_act"] == "silu" else 2
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + n_ffn * d * ffn
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"]


def attn_ops(c: dict, context: int) -> float:
    hd = c["hidden_size"] // c["num_attention_heads"]
    return 4.0 * hd * context * c["num_attention_heads"] * c["num_hidden_layers"]


def token_ops(c: dict, context: int) -> float:
    """Operations to decode one token that attends ``context`` keys."""
    return 2.0 * matmul_params(c) + attn_ops(c, context)


def prefill_ops(c: dict, n: int) -> float:
    """A prompt of ``n`` real tokens: causal attention over 1..n keys,
    and the logits head once, for the last position."""
    body = 2.0 * (matmul_params(c) - c["hidden_size"] * c["vocab_size"]) * n
    attn = attn_ops(c, 1) * n * (n + 1) / 2
    return body + attn + 2.0 * c["hidden_size"] * c["vocab_size"]
