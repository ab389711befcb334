"""Percentile and window arithmetic over what the load client recorded.

Copied in spirit from ``launch/serve.py:run_traffic`` (TTFT timed from the
*scheduled* send, inter-token gaps from token receive times), with its
three faults mended: the client, not the engine loop, keeps the schedule
(so generator lateness counts against TTFT); failed, refused and
never-answered requests count as misses (+inf) instead of being dropped;
and only tokens received inside the window count towards the rate.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

INF = float("inf")


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default method); +inf
    values sort last, so a tail that reaches a miss reads +inf."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == INF:
        return INF if pos > lo or xs[lo] == INF else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ok(r: Dict[str, Any]) -> bool:
    """A request the server answered in full (HTTP 200, FINISHED)."""
    return r.get("status") == "FINISHED" and r.get("http") == 200


def in_window(r: Dict[str, Any], t0: float, t1: float) -> bool:
    """Scheduled (open loop) or sent (closed loop) inside [t0, t1)."""
    return t0 <= r["sched"] < t1


def ttft_values(reqs: List[Dict[str, Any]], t0: float, t1: float
                ) -> List[float]:
    """Seconds from each window request's scheduled send to its first
    token; a request with no first token, or that failed, is +inf."""
    out = []
    for r in reqs:
        if not in_window(r, t0, t1):
            continue
        first = r.get("first")
        bad = r.get("http") not in (None, 200) or r.get("status") not in (
            None, "FINISHED", "CUT")
        out.append(INF if first is None or bad else first - r["sched"])
    return out


def itl_values(reqs: List[Dict[str, Any]], t0: float, t1: float
               ) -> List[float]:
    """Every gap between consecutive token events of every request whose
    later event falls inside the window."""
    out = []
    for r in reqs:
        ts = r.get("times") or []
        for a, b in zip(ts, ts[1:]):
            if t0 <= b < t1:
                out.append(b - a)
    return out


def tokens_in_window(reqs: List[Dict[str, Any]], t0: float, t1: float
                     ) -> int:
    return sum(1 for r in reqs for t in (r.get("times") or [])
               if t0 <= t < t1)


def attempted_failed(reqs: List[Dict[str, Any]], t0: float, t1: float
                     ) -> tuple:
    """(attempted, failed) over the window's requests. A request cut at
    the window's close is not a failure, with or without a first token (a
    closed loop's queue is still waiting then); one that got an error, a
    non-200 answer, or ended in any state but FINISHED or CUT is."""
    att = fail = 0
    for r in reqs:
        if not in_window(r, t0, t1):
            continue
        att += 1
        if r.get("http") not in (None, 200) or r.get("status") not in (
                "FINISHED", "CUT"):
            fail += 1
    return att, fail


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50) if values else None
