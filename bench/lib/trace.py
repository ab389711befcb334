"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the whole program executions of the traced window with the
kernel time inside each, device busy time, the top device operations and
the longest idle gaps.

Reads the file with ``jax.profiler.ProfileData`` only. Device planes are
``/device:TPU:<n>``; on each, the ``XLA Modules`` line holds one event per
program execution (``jit_decode_sample(...)``) and the ``XLA Ops`` line
one event per operation, Pallas kernels under their own names
(``bcr_spmm``, ``bcr_spmm_grouped``, ``paged_attention``). The harness
marks the traced window with a host span ``bench:window`` and each decode
call with ``bench:decode:<k>`` (``k`` indexes its log of the call's live
contexts), so the reducer needs no assumption about the trace's time base.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

PROGRAMS = ("prefill_sample", "decode_sample", "append_sample",
            "verify_logits")
KERNELS = ("bcr_spmm_grouped", "bcr_spmm", "paged_attention")
HOST_PREFIX = "bench:"
WINDOW = HOST_PREFIX + "window"
DECODE_CALL = HOST_PREFIX + "decode:"

Event = Tuple[str, float, float]


def find_xplane(log_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    return hits[-1] if hits else None


def program_of(name: str) -> Optional[str]:
    for p in PROGRAMS:
        if p in name:
            return p
    return None


def instruction(name: str) -> str:
    """An op event's HLO instruction name: on a TPU the event is named by
    the instruction's text (``%bcr_spmm.14 = bf16[...] custom-call(...)``),
    which gives ``bcr_spmm.14``; a bare name is returned as it is."""
    return name.split(" = ", 1)[0].lstrip("%").split(" ", 1)[0]


def op_family(name: str) -> str:
    """An op's instruction name without its instance number
    (``%fusion.12 = ...`` -> fusion)."""
    return re.sub(r"\.\d+$", "", instruction(name))


def kernel_of(name: str) -> Optional[str]:
    """The Pallas kernel an op event belongs to, by its name."""
    fam = op_family(name)
    return fam if fam in KERNELS else None


def _events(line) -> List[Event]:
    return [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns)
             * 1e-9) for ev in line.events]


def load(path: str) -> Dict[str, Any]:
    """Device and harness host events of a trace (``.xplane.pb``, or the
    same gzipped), in seconds on one clock."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    dev: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            dev[plane.name] = {
                "modules": lines.get("XLA Modules", []),
                "ops": lines.get("XLA Ops", [])}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(e for e in _events(ln)
                            if e[0].startswith(HOST_PREFIX))
    return {"devices": dev, "host": host}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _covering(events: List[Event], t: float) -> Optional[str]:
    best = None
    for name, a, b in events:
        if a <= t <= b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else None


def window_of(raw: Dict[str, Any]) -> Tuple[float, float]:
    """The traced window: the harness's ``bench:window`` host span."""
    spans = [(a, b) for n, a, b in raw["host"] if n == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"trace holds {len(spans)} {WINDOW!r} host spans, "
                         f"not one")
    return spans[0]


def _executions(mods: List[Event], ops: List[Event], calls: List[Event],
                t0: float, t1: float) -> Dict[str, List[Dict[str, Any]]]:
    """Program executions lying wholly inside ``[t0, t1]``: each one's
    device seconds, kernel seconds inside it, and (decode) the index of
    the harness's decode call that launched it: the latest one that
    started before it did."""
    ops = sorted((a, b, n) for n, a, b in ops if kernel_of(n))
    starts = [a for a, _, _ in ops]
    calls = sorted((a, int(n[len(DECODE_CALL):])) for n, a, _ in calls)
    call_starts = [a for a, _ in calls]
    out: Dict[str, List[Dict[str, Any]]] = defaultdict(list)
    for n, a, b in sorted(mods, key=lambda e: e[1]):
        p = program_of(n)
        if p is None or a < t0 or b > t1:
            continue
        kern: Dict[str, float] = defaultdict(float)
        for oa, ob, on in ops[bisect.bisect_left(starts, a):]:
            if oa > b:
                break
            if ob <= b:
                kern[kernel_of(on)] += ob - oa
        ex = {"s": b - a, "kernels": dict(kern), "call": None}
        if p == "decode_sample":
            j = bisect.bisect_right(call_starts, a) - 1
            if j >= 0:
                ex["call"] = calls[j][1]
        out[p].append(ex)
    return dict(out)


def reduce(raw: Dict[str, Any]) -> Dict[str, Any]:
    """Numbers of the traced window (``window_of``). Busy time and idle
    gaps clip the device's operations to the window, so the idle share
    covers exactly its length; per-program numbers count only executions
    that lie wholly inside it, so a step cut by either edge counts neither
    in the step time nor in any share."""
    t_start, t_stop = window_of(raw)
    window = t_stop - t_start
    if not raw["devices"]:
        raise ValueError("trace holds no TPU device plane")
    evs = [e for d in raw["devices"].values() for e in d["ops"]]
    if not any(a < t_stop and b > t_start for _, a, b in evs):
        raise ValueError(f"no device operation overlaps the traced window "
                         f"[{t_start!r}, {t_stop!r}] s")
    calls = [e for e in raw["host"] if e[0].startswith(DECODE_CALL)]

    def clip(events):
        return [(n, max(a, t_start), min(b, t_stop)) for n, a, b in events
                if b > t_start and a < t_stop]
    per_dev = []
    for name in sorted(raw["devices"]):
        d = raw["devices"][name]
        ops = clip(d["ops"])
        busy = sum(b - a for a, b in union([(a, b) for _, a, b in ops]))
        fam: Dict[str, float] = defaultdict(float)
        for n, a, b in ops:
            fam[op_family(n)] += b - a
        per_dev.append({"busy_s": busy, "families": dict(fam), "ops": ops,
                        "modules": clip(d["modules"]),
                        "execs": _executions(d["modules"], d["ops"], calls,
                                             t_start, t_stop)})
    d0 = per_dev[0]
    # idle gaps on the first device, labelled by the programs around them
    # and by the harness's innermost host span that covers the gap's middle
    u = union([(a, b) for _, a, b in d0["ops"]])
    gaps = []
    mods = sorted(d0["modules"], key=lambda e: e[1])
    inner = [e for e in raw["host"] if e[0] != WINDOW]
    for (a0, b0), (a1, b1) in zip(u, u[1:]):
        before = _covering(mods, b0 - 1e-9)
        after = _covering(mods, a1 + 1e-9)
        label = (f"{program_of(before or '') or 'none'} -> "
                 f"{program_of(after or '') or 'none'}")
        hspan = _covering(inner, (b0 + a1) / 2)
        if hspan:
            label += f" [{hspan[len(HOST_PREFIX):].split(':')[0]}]"
        gaps.append((label, a1 - b0))
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(d0["families"].items(), key=lambda kv: -kv[1])[:10]
    execs = d0["execs"]
    return {
        "window_s": window,
        "devices": len(per_dev),
        "busy_s": sum(d["busy_s"] for d in per_dev) / len(per_dev),
        "execs": execs,
        "programs": {p: [e["s"] for e in v] for p, v in execs.items()},
        "device_ops": [[n, s] for n, s in top_ops],
        "idle_gaps": [[n, s] for n, s in gaps[:10]],
    }


def check_decode(red: Dict[str, Any]) -> None:
    """A traced serving run has to see whole decode executions with the
    kernels inside them; a name or a line that no longer matches would
    otherwise silence every roofline."""
    ex = red["execs"].get("decode_sample", [])
    if not ex:
        raise RuntimeError("the traced window holds no whole "
                           "decode_sample execution")
    if not any(e["kernels"] for e in ex):
        raise RuntimeError(f"no {KERNELS} event inside any decode_sample "
                           f"execution of the traced window")
    if all(e["call"] is None for e in ex):
        raise RuntimeError("no decode execution could be matched to the "
                           f"harness's {DECODE_CALL!r} host spans")
