"""Required work of a whole decode step: the BCR projections and LM head,
decode attention, and the embedding rows read (norms and elementwise work
are left out: they are a rounding error of the bytes)."""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from . import bcr_spmm, paged_attention


def work(m: Dict[str, Any], contexts: Sequence[int]) -> Tuple[float, float]:
    rows = len(contexts)
    f1, b1 = bcr_spmm.work(m, rows)
    f2, b2 = paged_attention.work(m, contexts)
    emb = rows * m["hidden_size"] * bcr_spmm.BF16
    return f1 + f2, b1 + b2 + emb


def ideal_s(flops: float, byts: float, peaks: Dict[str, float]):
    """(seconds, bound): the least time the chip could take, and whether
    compute or memory bandwidth bounds it."""
    tc = flops / peaks["bf16_flops"]
    tm = byts / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
