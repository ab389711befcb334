"""The one traffic generator: a mix file of parameters -> a request plan.

Stdlib only, so the load client (a child process that never imports JAX)
and the harness build the same plan from the same ``(mix, seed, vocab)``.

Every seed gets the same multiset of sizes and arrival gaps, in another
order: sizes are the mix distribution's quantiles at ``(j + 0.5) / B`` for
the ``B`` strata of a block, and each block of ``B`` consecutive requests
holds every stratum once, in an order drawn from the seed. So any window
that consumes whole blocks serves the same work under every seed, and the
seed changes only the order and the token ids. A mix that sets ``order_seed`` draws that order
from it instead, so every seed serves the same sequence of sizes and
changes only the token ids: for a closed loop at saturation, where the
first requests in the order decide which ones the page pool holds through
the whole window.
"""

from __future__ import annotations

import json
import math
import random
import statistics
from typing import Any, Dict, List

_NORMAL = statistics.NormalDist()


def load_mix(path: str) -> Dict[str, Any]:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    return mix


def quantile(dist: Dict[str, Any], p: float) -> int:
    """Quantile ``p`` of a length distribution, rounded and clipped."""
    kind = dist["dist"]
    if kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(p))
    elif kind == "fixed":
        v = dist["value"]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(dist["max"], max(dist["min"], round(v))))


def strata(dist: Dict[str, Any], n: int) -> List[int]:
    return [quantile(dist, (j + 0.5) / n) for j in range(n)]


def _block_order(rng: random.Random, n: int) -> List[int]:
    order = list(range(n))
    rng.shuffle(order)
    return order


def plan(mix: Dict[str, Any], seed: int, vocab: int, n: int
         ) -> List[Dict[str, Any]]:
    """The first ``n`` requests of the mix under ``seed``.

    Each request: ``i``, ``prompt_len``, ``max_tokens``, ``offset_s``
    (open loop: scheduled send time from the window's start; closed loop:
    0) and ``token_seed`` (see :func:`prompt_tokens`)."""
    b = int(mix["strata"])
    p_sizes = strata(mix["prompt"], b)
    o_sizes = strata(mix["output"], b)
    gaps = None
    if mix["loop"] == "open":
        rate = float(mix["rate"])
        # exponential inter-arrival quantiles, scaled so a block's gaps
        # sum to exactly b / rate (the stated mean rate)
        g = [-math.log(1.0 - (j + 0.5) / b) for j in range(b)]
        s = sum(g)
        gaps = [x * b / (rate * s) for x in g]
    rng = random.Random(seed)
    order = (random.Random(int(mix["order_seed"])) if "order_seed" in mix
             else rng)
    out: List[Dict[str, Any]] = []
    t = 0.0
    for blk in range(-(-n // b)):
        po, oo = _block_order(order, b), _block_order(order, b)
        go = _block_order(order, b) if gaps else None
        for j in range(b):
            i = blk * b + j
            if i >= n:
                break
            if gaps:
                t += gaps[go[j]]
            out.append({"i": i, "prompt_len": p_sizes[po[j]],
                        "max_tokens": o_sizes[oo[j]],
                        "offset_s": t if gaps else 0.0,
                        "token_seed": rng.getrandbits(63)})
    return out


def prompt_tokens(req: Dict[str, Any], vocab: int) -> List[int]:
    """The prompt's token ids, uniform over the vocabulary."""
    rng = random.Random(req["token_seed"])
    return [rng.randrange(vocab) for _ in range(req["prompt_len"])]


def window_requests(mix: Dict[str, Any], seconds: float) -> int:
    """How many requests the plan must hold for one run: an open loop's
    arrivals up to the window's end plus its warm-in, a closed loop's
    ``pool`` (sized in the mix to outlast a window at any rate the cell
    can reach)."""
    if mix["loop"] == "open":
        span = seconds + float(mix.get("warm_in_s", 0.0))
        return int(math.ceil(span * float(mix["rate"]))) + int(mix["strata"])
    return int(mix["pool"])


def prompt_lengths(mix: Dict[str, Any]) -> List[int]:
    """Every prompt length the mix can send (one per stratum)."""
    return strata(mix["prompt"], int(mix["strata"]))


def output_lengths(mix: Dict[str, Any]) -> List[int]:
    return strata(mix["output"], int(mix["strata"]))
