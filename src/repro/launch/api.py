"""HTTP serving entry point: the InferenceEngine behind an asyncio API.

Builds the same (BCR-packed, optionally paged / prefix-cached /
speculative) engine as ``launch/serve.py``, then serves it over
``serving/server.py``'s stdlib HTTP front-end instead of driving
synthetic traffic at it:

    PYTHONPATH=src python -m repro.launch.api --arch llama3.2-1b --smoke \\
        --slots 8 --page-size 16 --bcr-keep 0.25 --port 8080

    curl -N localhost:8080/v1/completions -d \\
        '{"prompt": [1, 2, 3], "max_tokens": 8, "stream": true}'

SIGTERM (or Ctrl-C) triggers graceful drain: readiness flips false, the
waiting queue is shed, in-flight requests finish and flush their streams,
and ``check_conservation()`` verifies nothing leaked before exit.
"""

from __future__ import annotations

import argparse
import asyncio
import sys
from typing import Optional, Sequence

from repro.launch.serve import build_engine, enable_compile_cache
from repro.serving import TenantQuota
from repro.serving.server import InferenceServer, ServerConfig


def parse_tenant_quotas(specs) -> dict:
    """Parse repeated ``--tenant NAME,KEY=V[,KEY=V...]`` CLI specs.

    Keys: rate (admits/s), burst, concurrent, pages, weight — e.g.
    ``--tenant acme,rate=5,burst=10,weight=2 --tenant free,rate=1``.
    """
    quotas = {}
    for spec in specs or ():
        name, _, rest = spec.partition(",")
        if not name:
            raise ValueError(f"--tenant {spec!r}: empty tenant name")
        kw = {}
        for item in filter(None, rest.split(",")):
            k, _, v = item.partition("=")
            key = {"rate": "rate", "burst": "burst",
                   "concurrent": "max_concurrent", "pages": "max_pages",
                   "weight": "weight"}.get(k.strip())
            if key is None:
                raise ValueError(f"--tenant {spec!r}: unknown key {k!r}")
            kw[key] = float(v) if key in ("rate", "weight") else int(v)
        quotas[name] = TenantQuota(**kw)
    return quotas


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--capacity", type=int, default=128)
    p.add_argument("--page-size", type=int, default=0)
    p.add_argument("--kv-pages", type=int, default=0)
    p.add_argument("--prefix-cache", action="store_true")
    p.add_argument("--spec-k", type=int, default=0)
    p.add_argument("--draft-d-model", type=int, default=0)
    p.add_argument("--draft-layers", type=int, default=2)
    p.add_argument("--draft-bcr-keep", type=float, default=0.0)
    p.add_argument("--bcr-keep", type=float, default=0.0)
    p.add_argument("--bcr-block", type=int, default=0)
    p.add_argument("--impl", default=None,
                   choices=["ref", "interpret", "pallas"],
                   help="kernel backend (default: pallas on a TPU, ref "
                        "elsewhere; ref is the pure-JAX oracle, interpret "
                        "is CPU-only)")
    p.add_argument("--kv-dtype", default="", choices=["", "int8"])
    p.add_argument("--weight-dtype", default="", choices=["", "int8"])
    p.add_argument("--mesh-model", type=int, default=1,
                   help="tensor-parallel mesh size: shard the engine over "
                        "this many devices (bit-identical greedy tokens; "
                        "needs --page-size on a dense/vlm arch whose head "
                        "counts divide the mesh)")
    p.add_argument("--max-waiting", type=int, default=0,
                   help="bound the waiting queue; overflow sheds the "
                        "lowest-tier earliest-deadline waiter as 429")
    p.add_argument("--preempt-after-stalls", type=int, default=0)
    p.add_argument("--slo-admission", action="store_true",
                   help="SLO-aware admission: 429 deadline-carrying "
                        "requests at submit when the seat-time estimator "
                        "says they cannot finish in time, with a computed "
                        "Retry-After")
    p.add_argument("--slo-slack", type=float, default=1.0,
                   help="admission slack: admit while estimated finish ≤ "
                        "slack × deadline")
    p.add_argument("--tenant", action="append", default=[],
                   metavar="NAME,KEY=V[,...]",
                   help="per-tenant quota (repeatable): keys rate "
                        "(admits/s), burst, concurrent, pages, weight — "
                        "e.g. --tenant acme,rate=5,burst=10,weight=2")
    p.add_argument("--default-tenant-quota", default="",
                   metavar="KEY=V[,...]",
                   help="quota applied to tenants without a --tenant "
                        "entry (same keys, no name)")
    p.add_argument("--stream-queue-max", type=int, default=256,
                   help="per-stream SSE high-water mark: past this many "
                        "undelivered tokens the slow-client policy "
                        "engages (0 → unbounded)")
    p.add_argument("--slow-client-policy", default="cancel",
                   choices=["cancel", "pause"],
                   help="what to do with a stalled SSE reader past the "
                        "high-water mark: cancel the request, or pause "
                        "its scheduling (freeing the slot) and resume "
                        "once the stream drains")
    p.add_argument("--no-keep-alive", action="store_true",
                   help="close every connection after one response "
                        "(HTTP keep-alive is on by default)")
    p.add_argument("--keepalive-idle-s", type=float, default=5.0,
                   help="drop keep-alive connections idle this long")
    p.add_argument("--max-conn-requests", type=int, default=100,
                   help="requests served per connection before the "
                        "server answers Connection: close")
    p.add_argument("--default-max-tokens", type=int, default=16)
    p.add_argument("--max-restarts", type=int, default=3,
                   help="supervisor budget: crashes tolerated per "
                        "--restart-window-s before giving up")
    p.add_argument("--restart-window-s", type=float, default=60.0)
    p.add_argument("--slow-steps-restart", type=int, default=0,
                   help="restart the step loop after this many NEW "
                        "watchdog-flagged slow steps (0 → off)")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip compile-ahead warmup (readiness flips "
                        "immediately; first requests pay jit)")
    p.add_argument("--warmup-lens", type=int, nargs="*", default=[16, 32],
                   help="prompt lengths to compile ahead of readiness")
    return p


def build_api_engine(args):
    """The engine ``main`` serves, from parsed :func:`build_parser` args:
    the shared ``serve.build_engine`` plus the per-tenant quotas only this
    entry point has."""
    return build_engine(
        args,
        tenant_quotas=parse_tenant_quotas(args.tenant) or None,
        default_tenant_quota=(
            parse_tenant_quotas(["_," + args.default_tenant_quota])["_"]
            if args.default_tenant_quota else None))


def main(argv: Optional[Sequence[str]] = None) -> None:
    p = build_parser()
    args = p.parse_args(argv)
    enable_compile_cache()
    try:
        engine = build_api_engine(args)
    except ValueError as e:
        p.error(str(e))
    server = InferenceServer(engine, ServerConfig(
        host=args.host, port=args.port,
        default_max_tokens=args.default_max_tokens,
        max_restarts=args.max_restarts,
        restart_window_s=args.restart_window_s,
        slow_steps_restart=args.slow_steps_restart,
        stream_queue_max=args.stream_queue_max,
        slow_client_policy=args.slow_client_policy,
        keep_alive=not args.no_keep_alive,
        keepalive_idle_s=args.keepalive_idle_s,
        max_requests_per_conn=args.max_conn_requests))
    warmup = None if args.no_warmup else args.warmup_lens
    print(f"serving {engine.cfg.name} on http://{args.host}:{args.port} "
          f"(slots={args.slots}, page_size={args.page_size}, "
          f"warmup={'off' if warmup is None else warmup})")
    try:
        asyncio.run(server.serve_forever(warmup))
    except KeyboardInterrupt:
        pass
    print("drained; conservation "
          + ("ok" if server.conservation_ok else "FAILED"))
    if not server.conservation_ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
