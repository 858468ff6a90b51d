"""Work and bytes of one paged flash-decode attention call.

One decode step of one layer: every live slot's query (``heads`` rows of
``head_dim``) against its ``live`` cached keys. Work counts the two
contractions (scores and values): ``4 * head_dim * live`` operations per
query row and head. Bytes are the live K and V codes (1 byte each) and
their f32 per-entry scales for each KV head, the FP8 query codes and the
f32 output.
"""

from __future__ import annotations

from typing import Iterable


def ops(lives: Iterable[int], heads: int, head_dim: int) -> float:
    return float(sum(4 * head_dim * heads * live for live in lives))


def hbm_bytes(lives: Iterable[int], heads: int, kv_heads: int,
              head_dim: int) -> float:
    total = 0
    for live in lives:
        kv = 2 * kv_heads * live * (head_dim + 4)   # K,V codes + scales
        q = heads * head_dim + 4 * kv_heads         # query codes + scales
        out = 4 * heads * head_dim                  # f32 output
        total += kv + q + out
    return float(total)


def least_s(lives, heads: int, kv_heads: int, head_dim: int,
            peak: dict) -> float:
    lives = list(lives)
    return max(ops(lives, heads, head_dim) / peak["int8_ops_per_s"],
               hbm_bytes(lives, heads, kv_heads, head_dim)
               / peak["hbm_bytes_per_s"])
