"""The fused-matmul calls a dense decoder makes, as ``(M, K, N)`` triples
with the real rows ``M``, read off the model's structure.

Per layer: the q, k, v and output projections and the FFN projections.
A prefill also runs the chunked attention's score and value contractions
through the same kernel (per KV head and key chunk of ``attn_chunk``
keys), and the logits head once, for the last position; a decode step
runs the logits head for every live slot.
"""

from __future__ import annotations

from typing import List, Tuple

ATTN_CHUNK = 1024


def _layer(c: dict, m: int) -> List[Tuple[int, int, int]]:
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, f = d // h, c["intermediate_size"]
    ffn = ([(m, d, f), (m, d, f), (m, f, d)] if c["hidden_act"] == "silu"
           else [(m, d, f), (m, f, d)])
    return [(m, d, h * hd), (m, d, kv * hd), (m, d, kv * hd),
            (m, h * hd, d)] + ffn


def prefill_matmuls(c: dict, plen: int, bucket: int):
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, g = d // h, h // kv
    chunks = -(-bucket // ATTN_CHUNK)
    attn = [(plen * g, hd, ATTN_CHUNK), (plen * g, ATTN_CHUNK, hd)] * (kv * chunks)
    return (_layer(c, plen) + attn) * c["num_hidden_layers"] + [
        (1, d, c["vocab_size"])]


def decode_matmuls(c: dict, live: int):
    return _layer(c, live) * c["num_hidden_layers"] + [
        (live, c["hidden_size"], c["vocab_size"])]
