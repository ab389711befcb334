"""Required work of the BCR projections (``bcr_spmm``, ``bcr_spmm_grouped``)
in one decode step: every projection of every layer plus the LM head.

Counted from the configuration, not from the implementation, so no later
change of format can push a share past 100%: kept weights are
``keep x N x K`` values read once at bf16 (the precision the model
computes in), whatever the program stores; index planes and padding are
not counted. Flops are ``2 x live rows x kept weights``; activations in
and out are read and written once at bf16.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

BF16 = 2


def matrices(m: Dict[str, Any]) -> List[Tuple[int, int]]:
    """(N, K) of every projection of one layer, then nothing else."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hq = m["num_attention_heads"] * m["head_dim"]
    hkv = m["num_key_value_heads"] * m["head_dim"]
    return [(hq, d), (hkv, d), (hkv, d), (d, hq), (f, d), (f, d), (d, f)]


def all_matrices(m: Dict[str, Any]) -> List[Tuple[int, int]]:
    return (matrices(m) * m["num_hidden_layers"]
            + [(m["vocab_size"], m["hidden_size"])])


def work(m: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """(flops, bytes) of one decode step with ``rows`` live rows."""
    keep = float(m["bcr_keep"])
    flops = byts = 0.0
    for n, k in all_matrices(m):
        kept = keep * n * k
        flops += 2.0 * rows * kept
        byts += kept * BF16 + rows * (n + k) * BF16
    return flops, byts
