"""The program's host spans of a traced run, with their args.

The engine and its HTTP front end record host spans on the profiler's
clock (``src/repro/serving/tracing.py``): ``engine.step`` (args ``step``,
``live``, ``waiting`` and the KV-page gauges ``kv_pages_in_use``,
``kv_pages_reserved``, ``kv_pages_free``) around ``engine.admit``,
``engine.prefill``, ``engine.decode.dispatch`` (``step``, ``rows``,
``ctx_tokens``), ``engine.decode.wait`` and ``engine.commit`` or
``engine.spec``; beside the steps, ``server.pump`` and ``server.idle``.
They land in the same ``.xplane.pb`` as the device's programs.

``for_run(ctx)`` loads them, with the harness's ``bench:window`` and
``bench:decode:<k>`` spans, from the newest trace under ``bench/.traces/``
(the run's context holds no path) and refuses a trace that is not the
run's own: every decode call it names must be in the run's decode log,
and its window must be the one the reducer measured. A program that
records no such span (one older than them) gives None, and so do the
readers; a trace that holds some of them but no ``engine.step`` is a
fault. The metrics ``host_gap_ms``, ``pump_ms`` and ``kv_reserved_share``
read them.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import sys
from collections import defaultdict, namedtuple
from typing import Any, Dict, List, Optional

from lib.trace import DECODE_CALL, WINDOW

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = os.path.join(BENCH, ".traces")
PROGRAM = ("engine.", "server.")
STEP = "engine.step"
CHILDREN = ("engine.admit", "engine.prefill", "engine.decode.dispatch",
            "engine.decode.wait", "engine.commit", "engine.spec")
DISPATCH, WAIT = "engine.decode.dispatch", "engine.decode.wait"
GAUGES = ("kv_pages_in_use", "kv_pages_reserved", "kv_pages_free")

Span = namedtuple("Span", "name a b args")
#: where ``for_run`` keeps the loaded trace in the run's context, so the
#: readers of one run parse it once
CTX_KEY = "program_spans"


def newest() -> Optional[str]:
    """The newest trace file under ``bench/.traces/``."""
    hits = glob.glob(os.path.join(TRACES, "*", "plugins", "profile", "*",
                                  "*.xplane.pb"))
    return max(hits, key=os.path.getmtime) if hits else None


def load(path: str) -> Dict[str, Any]:
    """The program's spans (times in seconds, args as recorded), the
    ``bench:window`` span and the decode-call indices of a trace file
    (``.xplane.pb``, or the same gzipped)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    spans: List[Span] = []
    windows, calls = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                a = ev.start_ns * 1e-9
                b = (ev.start_ns + ev.duration_ns) * 1e-9
                if name.startswith(PROGRAM):
                    spans.append(Span(name, a, b, dict(ev.stats)))
                elif name == WINDOW:
                    windows.append((a, b))
                elif name.startswith(DECODE_CALL):
                    calls.append(int(name[len(DECODE_CALL):]))
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {WINDOW!r} spans, "
                         f"not one")
    spans.sort(key=lambda s: (s.a, -s.b))
    return {"path": path, "window": windows[0], "spans": spans,
            "calls": sorted(calls)}


def check(run: Dict[str, Any], ctx: Dict[str, Any]) -> None:
    """Refuse a trace that is not the run's own."""
    stray = sorted(set(run["calls"]) - set(ctx["decode_log"]))
    if stray:
        raise ValueError(f"{run['path']}: decode calls {stray[:5]} are not "
                         f"in this run's decode log")
    a, b = run["window"]
    if abs((b - a) - ctx["trace"]["window_s"]) > 1e-6:
        raise ValueError(f"{run['path']}: window of {b - a!r} s, but the "
                         f"run traced {ctx['trace']['window_s']!r} s")


def for_run(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The traced run's spans (``load``), checked against ``ctx`` and kept
    in it; None for an untraced run or a program that records no span.
    The first load prints each span's self time (``self_times``) to
    stderr."""
    if not ctx.get("trace"):
        return None
    path = newest()
    if path is None:
        raise FileNotFoundError(f"a traced run left no trace under "
                                f"{TRACES}")
    run = ctx.get(CTX_KEY)
    if run is None or run["path"] != path:
        run = load(path)
        check(run, ctx)
        if run["spans"]:
            if not any(s.name == STEP for s in run["spans"]):
                raise ValueError(f"{path}: the program's spans hold no "
                                 f"{STEP!r}")
            for line in self_times(run):
                print(line, file=sys.stderr, flush=True)
        ctx[CTX_KEY] = run
    return run if run["spans"] else None


def inside(run: Dict[str, Any], name: str) -> List[Span]:
    """Spans called ``name`` that lie wholly inside the traced window."""
    t0, t1 = run["window"]
    return [s for s in run["spans"]
            if s.name == name and t0 <= s.a and s.b <= t1]


def steps(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Engine steps wholly inside the window, in order: each one's span,
    ``step`` arg and child spans by name (those that lie within it)."""
    kids = [s for s in run["spans"] if s.name in CHILDREN]
    starts = [s.a for s in kids]
    out = []
    for st in inside(run, STEP):
        by_name: Dict[str, List[Span]] = defaultdict(list)
        for k in kids[bisect.bisect_left(starts, st.a):]:
            if k.a > st.b:
                break
            if k.b <= st.b:
                by_name[k.name].append(k)
        out.append({"span": st, "step": st.args["step"],
                    "kids": dict(by_name)})
    return out


def self_times(run: Dict[str, Any]) -> List[str]:
    """Mean self time per decode step of each span in the window (ms):
    the table PERF.md quotes; ``engine.step`` counts only what its
    children leave."""
    st = steps(run)
    n = sum(1 for s in st if DISPATCH in s["kids"])
    if not n:
        return [f"spans: no decode step inside the window of {run['path']}"]
    tot: Dict[str, float] = defaultdict(float)
    for s in st:
        own = s["span"].b - s["span"].a
        for name, ks in s["kids"].items():
            d = sum(k.b - k.a for k in ks)
            tot[name] += d
            own -= d
        tot[STEP + " (self)"] += own
    for name in ("server.pump", "server.idle"):
        tot[name] = sum(s.b - s.a for s in inside(run, name))
    return [f"spans: {name} {1e3 * t / n!r} ms per decode step"
            for name, t in tot.items()] + [f"spans: {n} decode steps"]
