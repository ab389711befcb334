"""Required work of decode attention (``paged_attention``) in one step.

Each live row attends over its live context (the tokens already cached
plus the one being decoded): K and V of that context are read once at the
cache dtype (bf16), in every attention layer; padded pages and dead rows
are not counted. Flops: ``4 x context x query heads x head_dim`` per row
and layer (QK^T and PV).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

BF16 = 2


def work(m: Dict[str, Any], layers: int, rows: int, ctx_tokens: int
         ) -> Tuple[float, float]:
    """(flops, bytes) of one decode step in which ``rows`` live rows attend
    over ``ctx_tokens`` tokens in all, in each of ``layers`` layers."""
    hq, hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    ctx = float(ctx_tokens)
    flops = layers * 4.0 * ctx * hq * dh
    byts = layers * (ctx * 2 * hkv * dh * BF16 + rows * 2 * hq * dh * BF16)
    return flops, byts
