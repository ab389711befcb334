"""Required work of decode attention (``paged_attention``) in one step.

Each live row attends over its live context (the tokens already cached
plus the one being decoded): K and V of that context are read once at the
cache dtype (bf16), for every layer; padded pages and dead rows are not
counted. Flops: ``4 x context x query heads x head_dim`` per row and
layer (QK^T and PV).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

BF16 = 2


def work(m: Dict[str, Any], contexts: Sequence[int]) -> Tuple[float, float]:
    """(flops, bytes) of one decode step whose live rows attend over
    ``contexts`` tokens each."""
    hq, hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    layers = m["num_hidden_layers"]
    ctx = float(sum(contexts))
    rows = len(contexts)
    flops = layers * 4.0 * ctx * hq * dh
    byts = layers * (ctx * 2 * hkv * dh * BF16 + rows * 2 * hq * dh * BF16)
    return flops, byts
