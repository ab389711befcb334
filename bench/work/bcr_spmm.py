"""Required work of the BCR projections (``bcr_spmm``, ``bcr_spmm_grouped``)
in one decode step, over the projections a family lists.

Counted from the configuration, not from the implementation, so no later
change of format can push a share past 100%: kept weights are
``keep x N x K`` values read once at bf16 (the precision the model
computes in), whatever the program stores; index planes and padding are
not counted. Flops are ``2 x live rows x kept weights``; activations in
and out are read and written once at bf16.
"""

from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def work(matrices: Iterable[Tuple[int, int]], keep: float, rows: int
         ) -> Tuple[float, float]:
    """(flops, bytes) of one decode step with ``rows`` live rows through
    every ``(N, K)`` projection of ``matrices`` at kept density ``keep``."""
    flops = byts = 0.0
    for n, k in matrices:
        kept = keep * n * k
        flops += 2.0 * rows * kept
        byts += kept * BF16 + rows * (n + k) * BF16
    return flops, byts
