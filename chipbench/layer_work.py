"""The work single layers need, from the configuration's shapes and the
arguments the program's dispatch spans carry, by the rules of
``work.py``: attention is 2 * 2 * heads * head_dim operations per
(query, key), the key/value history of live positions is read once, and
new keys and values are written once."""
from __future__ import annotations

from chipbench.work import Shapes, Work


def decode_attention(s: Shapes, keys: int, rows: int) -> Work:
    """Attention of ``rows`` decode rows that attend ``keys`` keys in
    all (each row its history and itself, capped at the window): every
    key's operations, and every attended key's K and V read once except
    each row's own, which the dispatch computes (as ``work.decode``)."""
    return Work(float(s._attn_flops(keys)),
                float((keys - rows) * s.kv_bytes_per_position))


def kv_write(s: Shapes, new_tokens: int) -> Work:
    """Keys and values of ``new_tokens`` positions written once."""
    return Work(0.0, float(new_tokens * s.kv_bytes_per_position))
