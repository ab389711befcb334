"""Serving entry point: continuous-batching engine over BCR-packed weights.

The GRIM deployment path: take (ADMM-pruned) dense weights → pack every
prunable projection into TBCRC (kernel format) → serve a continuous-batching
decode loop whose weight traffic is keep_frac × dense. The kernel backend
is derived from the platform (:func:`select_backend`): on a TPU the compiled
Pallas kernels serve by default, elsewhere the pure-JAX references do;
``--impl`` overrides it (``ref`` is the oracle on any platform, ``interpret``
runs the kernel bodies in Pallas interpret mode and is CPU-only).

Two modes:

  traffic (default) — synthetic Poisson-arrival open-loop driver against the
  InferenceEngine: requests with mixed prompt lengths arrive at --rate req/s,
  are admitted into free decode slots, and retire as they finish. Reports
  throughput plus p50/p95/p99 per-token latency and TTFT.

      PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
          --slots 8 --rate 8 --requests 32 --gen 16 --bcr-keep 0.25

  static — the legacy one-batch-at-a-time loop (prefill + uniform greedy
  decode), kept as the baseline the engine is measured against:

      PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
          --mode static --batch 4 --prompt-len 16 --gen 16
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ModelConfig
from repro.core.bcr import BCRSpec, kept_align
from repro.core.bcrc import tbcrc_pack
from repro.kernels.ops import default_impl
from repro.launch.train import default_prune_filter
from repro.models.api import model_fns
from repro.serving import EngineConfig, InferenceEngine
from repro.serving.kv_slots import seat_prefill
from repro.serving.scheduler import FINISHED

PyTree = Any


def _pack_any(w: jax.Array, spec: BCRSpec):
    if w.ndim == 2:
        return tbcrc_pack(w, spec)
    return jax.vmap(lambda x: _pack_any(x, spec))(w)


def _auto_block_spec(spec: BCRSpec, shape, keep_frac: float, decode_m: int,
                     run_layer=None, _cache={}) -> BCRSpec:
    """keep_frac-aware block-size selection (GRIM §5.1, Listing 1) at pack
    time: sweep candidate block sizes with ``block_search.find_opt_blk``
    for THIS layer's (M, K, N, keep_frac) and take its verdict instead of
    the config's block as-is (block 128 beats 32 by ~3x on the CPU ref
    path at serving keep_fracs). Memoized per unique layer geometry."""
    from repro.core.block_search import (analytic_tpu_latency,
                                         default_candidates, find_opt_blk)
    n, k = int(shape[0]), int(shape[1])
    run_layer = run_layer or analytic_tpu_latency
    key = (n, k, keep_frac, decode_m, run_layer)
    if key not in _cache:
        cands = {c for c in default_candidates(n, k)}
        cands |= {(b, b) for b in (16, 32, 64, 128, 256)
                  if n % b == 0 and k % b == 0}
        cands.add(spec.block_shape)
        best, _ = find_opt_blk(decode_m, k, n, keep_frac, sorted(cands),
                               run_layer=run_layer)
        _cache[key] = best
    block = _cache[key]
    return BCRSpec(block_shape=block, keep_frac=keep_frac,
                   align=kept_align(block))


def pack_params(cfg: ModelConfig, params: PyTree, *, plan: bool = True,
                decode_m: int = 8, auto_block: bool = False,
                block_runner=None, plan_fitness: str = "analytic",
                weight_dtype: str = "") -> PyTree:
    """Replace every prunable linear's {"w"} with {"w_packed": TBCRC}.

    With ``plan=True`` (default) this is GRIM's full compile step: every
    packed weight gets a GA-tuned pack-time execution plan and projections
    sharing one activation (Q/K/V, gate/up) are fused into grouped
    dispatches (kernels/plan.py). ``decode_m`` is the decode-batch hint the
    tuner optimizes for.

    ``auto_block=True`` runs the paper's Listing-1 block-size search per
    layer geometry before packing (``block_runner`` overrides the latency
    backend — e.g. ``block_search.wallclock_cpu_runner``); the config's
    ``bcr_block`` then only seeds the candidate set. ``plan_fitness``
    selects the GA tuner's fitness backend ("analytic" roofline, default,
    or "wallclock" host timing).

    ``weight_dtype="int8"`` quantizes every packed tile to int8 codes plus
    a per-block fp32 scale (applied in the kernels' epilogue) before plan
    tuning, so the tuner's roofline prices the halved weight bytes.
    """
    fil = default_prune_filter(cfg)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    # group leaves by parent linear dict: handled structurally instead —
    # walk the tree and rewrite dicts that look like linear params.
    def rewrite(node, path=()):
        if isinstance(node, dict) and "w" in node and isinstance(
                node["w"], (jax.Array, jnp.ndarray)):
            leafpath = path + (jax.tree_util.DictKey("w"),)
            spec = fil(leafpath, node["w"])
            if spec is not None:
                if auto_block:
                    spec = _auto_block_spec(
                        spec, node["w"].shape[-2:], cfg.bcr_keep_frac,
                        decode_m, block_runner)
                out = {"w_packed": _pack_any(node["w"], spec)}
                if "b" in node:
                    out["b"] = node["b"]
                return out
        if isinstance(node, dict):
            return {k: rewrite(v, path + (jax.tree_util.DictKey(k),))
                    for k, v in node.items()}
        if isinstance(node, list):
            return [rewrite(v, path + (jax.tree_util.SequenceKey(i),))
                    for i, v in enumerate(node)]
        return node

    packed = rewrite(params)
    if weight_dtype:
        if weight_dtype != "int8":
            raise ValueError(f"unsupported weight_dtype {weight_dtype!r}")
        from repro.kernels.plan import quantize_packed_params
        packed = quantize_packed_params(packed)
    if plan:
        from repro.kernels.plan import plan_params
        packed = plan_params(packed, m=decode_m, fitness=plan_fitness,
                             fitness_impl=cfg.kernel_impl)
    return packed


def packed_fraction(params: PyTree, packed: PyTree) -> float:
    from repro.core.bcrc import TBCRC
    def nbytes(t):
        tot = 0
        for leaf in jax.tree_util.tree_leaves(
                t, is_leaf=lambda x: isinstance(x, TBCRC)):
            tot += (leaf.nbytes() if isinstance(leaf, TBCRC)
                    else leaf.size * leaf.dtype.itemsize)
        return tot
    return nbytes(packed) / nbytes(params)


# ---------------------------------------------------------------------------
# Legacy static-batch path (baseline; also the prefill regression surface)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jitted_fns(cfg: ModelConfig):
    """Per-config jit cache: repeated generate() calls (benchmark chunks)
    reuse compiled prefill/decode instead of re-tracing every call (jit
    caches are keyed on function identity, and model_fns builds fresh
    lambdas each time)."""
    fns = model_fns(cfg)
    return fns, jax.jit(fns.prefill), jax.jit(fns.decode_step)


@dataclasses.dataclass
class ServeConfig:
    batch: int = 4
    prompt_len: int = 16
    gen_tokens: int = 16
    capacity: int = 128
    seed: int = 0


def generate(cfg: ModelConfig, params: PyTree, sc: ServeConfig, log=print
             ) -> Dict[str, Any]:
    """Prefill a batch of prompts, then greedy-decode gen_tokens.

    Prompt ingestion uses the real batched ``prefill`` (one forward pass),
    not the old O(prompt_len)-dispatch single-step loop; the prefill cache
    (seq axis = prompt length) is seated into a capacity-sized decode cache.
    """
    if cfg.family == "encdec":
        raise NotImplementedError(
            "generate() serves decoder-only families; encdec prefill needs "
            "encoder frames and primes a different cache tree")
    fns, prefill, decode = _jitted_fns(cfg)
    key = jax.random.PRNGKey(sc.seed)
    prompts = jax.random.randint(
        key, (sc.batch, sc.prompt_len), 0, cfg.vocab_size, jnp.int32)

    t0 = time.perf_counter()
    logits, pcache = prefill(params, {"tokens": prompts})
    cache = seat_prefill(fns.init_cache, pcache, sc.batch, sc.capacity)
    jax.block_until_ready(logits)
    prefill_t = time.perf_counter() - t0
    lens = jnp.full((sc.batch,), sc.prompt_len, jnp.int32)
    out_tokens = []
    t0 = time.perf_counter()
    next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    for i in range(sc.gen_tokens):
        out_tokens.append(next_tok)
        batch = {"tokens": next_tok, "cache_len": lens + i}
        logits, cache = decode(params, batch, cache)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    jax.block_until_ready(logits)
    decode_t = time.perf_counter() - t0

    toks = jnp.concatenate(out_tokens, axis=1)
    log(f"prefill {sc.prompt_len} tok x{sc.batch}: {prefill_t*1e3:.1f} ms; "
        f"decode {sc.gen_tokens} tok x{sc.batch}: {decode_t*1e3:.1f} ms "
        f"({decode_t/sc.gen_tokens*1e3:.2f} ms/step)")
    return {"tokens": toks, "prefill_s": prefill_t, "decode_s": decode_t}


# ---------------------------------------------------------------------------
# Poisson open-loop traffic driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrafficConfig:
    n_requests: int = 32
    rate: float = 8.0                # mean arrivals per second
    prompt_lens: tuple = (8, 16, 24)
    gen_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    warmup: bool = True
    # shared-prefix workload: each request = one of `system_prompts`
    # fixed system prompts of `system_len` tokens + a per-request user
    # suffix drawn from prompt_lens (the millions-of-users-few-prompts
    # serving shape the prefix cache targets). 0 → fully random prompts.
    system_prompts: int = 0
    system_len: int = 32
    # lifecycle knobs: deadline_s > 0 arms a per-request deadline (TIMEOUT
    # past it, waiting or running); cancel_rate > 0 cancels that fraction
    # of requests at a random point after their arrival — both exercise
    # the engine's terminal-status machinery under real traffic
    deadline_s: float = 0.0
    cancel_rate: float = 0.0
    # trace replay: a list of records (see load_trace) overrides the
    # Poisson arrival process — per-record arrival offset, prompt length,
    # max_new_tokens, priority, deadline and tenant drive the run instead
    trace: Any = None
    # multi-tenant traffic: Poisson-mode requests are tagged round-robin
    # from this tuple (empty → untagged); trace records carry their own
    # "tenant". Tags feed per-tenant quota/WFQ enforcement in the engine
    # and the per-tenant breakdown in the returned metrics.
    tenants: tuple = ()


def load_trace(path: str) -> List[Dict[str, Any]]:
    """Parse a jsonl request trace for :func:`run_traffic`.

    One JSON object per line::

        {"t": 0.12, "prompt_len": 16, "max_new_tokens": 16,
         "priority": 1, "deadline_s": 2.0, "tenant": "acme"}

    ``t`` (arrival offset in seconds from the run start) is required and
    must be non-decreasing; everything else defaults (prompt_len 16,
    max_new_tokens/deadline from the TrafficConfig, priority 0).
    """
    trace: List[Dict[str, Any]] = []
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            rec = json.loads(line)
            if "t" not in rec:
                raise ValueError(f"{path}:{ln}: trace record needs 't' "
                                 f"(arrival offset in seconds)")
            trace.append(rec)
    if any(b["t"] < a["t"] for a, b in zip(trace, trace[1:])):
        raise ValueError(f"{path}: arrival offsets must be non-decreasing")
    return trace


def run_traffic(engine: InferenceEngine, tc: TrafficConfig, log=print
                ) -> Dict[str, Any]:
    """Open-loop Poisson arrivals against a live engine, wall-clock paced.

    Requests with mixed prompt lengths arrive at exponential inter-arrival
    gaps; the loop admits whatever has arrived, steps the ragged decode
    batch, and sleeps only when fully idle ahead of the next arrival.
    """
    rng = np.random.default_rng(tc.seed)
    if tc.trace is not None:
        # trace replay: arrivals, prompt lengths, generation budgets,
        # priorities and deadlines all come from the records
        n_requests = len(tc.trace)
        arrivals = np.asarray([float(r["t"]) for r in tc.trace])
        plens = np.asarray([int(r.get("prompt_len", 16))
                            for r in tc.trace])
        gens = [int(r.get("max_new_tokens", tc.gen_tokens))
                for r in tc.trace]
        prios = [int(r.get("priority", 0)) for r in tc.trace]
        deadlines = [float(r.get("deadline_s", tc.deadline_s))
                     for r in tc.trace]
        tenants = [str(r.get("tenant", "")) for r in tc.trace]
        # clamp so no record can exceed its slot (prompt + gen + spec
        # headroom ≤ capacity) — a trace is a workload shape, not a
        # rejection test
        cap = engine.ec.capacity - engine._headroom()
        plens = np.asarray([max(1, min(int(p), cap - g))
                            for p, g in zip(plens, gens)])
    else:
        n_requests = tc.n_requests
        gaps = rng.exponential(1.0 / tc.rate, size=n_requests)
        arrivals = np.cumsum(gaps)
        plens = rng.choice(tc.prompt_lens, size=n_requests)
        gens = [tc.gen_tokens] * n_requests
        prios = [0] * n_requests
        deadlines = [tc.deadline_s] * n_requests
        tenants = ([tc.tenants[i % len(tc.tenants)]
                    for i in range(n_requests)] if tc.tenants
                   else [""] * n_requests)
    if tc.system_prompts > 0:
        systems = [rng.integers(0, engine.cfg.vocab_size,
                                size=tc.system_len).astype(np.int32)
                   for _ in range(tc.system_prompts)]
        prompts = [np.concatenate([
            systems[int(rng.integers(tc.system_prompts))],
            rng.integers(0, engine.cfg.vocab_size, size=int(p))
            .astype(np.int32)]) for p in plens]
    else:
        prompts = [rng.integers(0, engine.cfg.vocab_size, size=int(p))
                   .astype(np.int32) for p in plens]
    if tc.warmup:
        # compile prefill buckets + decode (and, with prefix sharing, the
        # suffix-append buckets) outside the measured window, else
        # TTFT/p99 report jit time instead of serving latency
        # suffix lengths at a hit: the user suffix plus up to a page of
        # unmatched system tail (plus the 1-token full-hit case)
        sfx = (tuple(int(p) + engine.ec.page_size for p in plens) + (1,)
               if tc.system_prompts else None)
        engine.warmup([len(p) for p in prompts], suffix_lens=sfx)

    # client-side cancellations: each request independently gets a cancel
    # scheduled at a random point after its arrival (within its deadline
    # window when one is set). Cancels racing completion are no-ops.
    cancel_at = np.full(n_requests, np.inf)
    if tc.cancel_rate > 0:
        hit = rng.random(n_requests) < tc.cancel_rate
        span = tc.deadline_s if tc.deadline_s > 0 else 0.5
        cancel_at[hit] = arrivals[hit] + rng.uniform(
            0.01, max(span, 0.02), size=int(hit.sum()))

    t0 = time.perf_counter()
    submitted = 0
    rids: List[int] = []
    while submitted < n_requests or engine.sched.has_work():
        now = time.perf_counter() - t0
        while submitted < n_requests and arrivals[submitted] <= now:
            rids.append(engine.submit(
                prompts[submitted], max_new_tokens=gens[submitted],
                temperature=tc.temperature, top_k=tc.top_k,
                arrival_time=arrivals[submitted],
                deadline_s=deadlines[submitted],
                priority=prios[submitted],
                tenant=tenants[submitted]))
            submitted += 1
        for i in np.nonzero(cancel_at <= now)[0]:
            if i < submitted:
                engine.cancel(rids[i])
                cancel_at[i] = np.inf
        if not engine.sched.has_work():
            # idle: sleep until the next arrival instead of spinning
            time.sleep(max(0.0, arrivals[submitted] - now))
            continue
        engine.step()
    elapsed = time.perf_counter() - t0

    reqs = engine.sched.finished
    fin = [r for r in reqs if r.status == FINISHED]
    itl: List[float] = []                      # inter-token latencies
    ttft: List[float] = []                     # arrival → first token
    # latency percentiles cover FINISHED requests only: a shed/timed-out
    # request has no meaningful TTFT, and mixing partial generations into
    # the ITL tail would flatter overloaded runs
    for r in fin:
        ttft.append((r.first_token_time - t0) - r.arrival_time)
        itl.extend(np.diff(r.token_times))
    total_tokens = sum(len(r.generated) for r in reqs)
    good_tokens = sum(len(r.generated) for r in fin)
    prompt_tokens = sum(r.prompt_len for r in reqs)
    status_counts = {
        s.lower(): sum(1 for r in reqs if r.status == s)
        for s in ("FINISHED", "TIMEOUT", "CANCELLED", "REJECTED", "FAILED")}
    pct = lambda a, q: float(np.percentile(a, q)) if len(a) else 0.0
    occ = engine.stats["slot_occupancy"]
    st = engine.stats
    metrics = {
        "n_requests": len(reqs),
        "total_tokens": total_tokens,
        "elapsed_s": elapsed,
        "throughput_tok_s": total_tokens / elapsed,
        # goodput counts tokens from FINISHED requests only — work spent
        # on requests that later timed out / cancelled / failed is waste
        "goodput_tok_s": good_tokens / elapsed,
        "status_counts": status_counts,
        "preempted": st["preemptions"],
        "shed": st["shed"],
        "decode_steps": engine.stats["decode_steps"],
        # per-DECODE-step commit rate: each request's first token is
        # prefill-sampled and never passed through a decode step, so it
        # is excluded — with speculation this is the payoff figure
        "decode_tokens_per_step": (
            max(total_tokens - len(reqs), 0)
            / max(engine.stats["decode_steps"], 1)),
        "mean_slot_occupancy": float(np.mean(occ)) if occ else 0.0,
        "ttft_s": {"p50": pct(ttft, 50), "p95": pct(ttft, 95),
                   "p99": pct(ttft, 99)},
        "per_token_s": {"p50": pct(itl, 50), "p95": pct(itl, 95),
                        "p99": pct(itl, 99)},
        "page_stalls": st["page_stalls"],
        "prefix_hit_rate": (st["prefix_hit_tokens"] / prompt_tokens
                            if prompt_tokens else 0.0),
        "prefix_hit_tokens": st["prefix_hit_tokens"],
        "pages_shared": st["pages_shared"],
        "cow_copies": st["cow_copies"],
        "evictions": st["evictions"],
        "spec_steps": st["spec_steps"],
        "draft_proposed": st["draft_proposed"],
        "draft_accepted": st["draft_accepted"],
        "acceptance_rate": (st["draft_accepted"] / st["draft_proposed"]
                            if st["draft_proposed"] else 0.0),
        # overload/SLO accounting: predictive admission turns would-be
        # queue timeouts into immediate rejects and keeps prefill work
        # from being wasted on doomed requests
        "slo_rejected": st.get("slo_rejected", 0),
        "quota_rejected": st.get("quota_rejected", 0),
        "timeouts_waiting": st.get("timeouts_waiting", 0),
        "timeouts_running": st.get("timeouts_running", 0),
        "wasted_prefill_tokens": st.get("wasted_prefill_tokens", 0),
        "tenants": {t: dict(v)
                    for t, v in st.get("tenants", {}).items()},
    }
    log(f"{len(reqs)} requests, {total_tokens} tokens in {elapsed:.2f}s "
        f"→ {metrics['throughput_tok_s']:.1f} tok/s; "
        f"mean occupancy {metrics['mean_slot_occupancy']:.2f}/"
        f"{engine.ec.n_slots} slots")
    log(f"status: finished {status_counts['finished']} / "
        f"timeout {status_counts['timeout']} / "
        f"cancelled {status_counts['cancelled']} / "
        f"rejected {status_counts['rejected']} / "
        f"preempted {st['preemptions']} / failed {status_counts['failed']}; "
        f"goodput {metrics['goodput_tok_s']:.1f} tok/s (FINISHED only)")
    log(f"TTFT p50/p95/p99: {metrics['ttft_s']['p50']*1e3:.1f}/"
        f"{metrics['ttft_s']['p95']*1e3:.1f}/"
        f"{metrics['ttft_s']['p99']*1e3:.1f} ms; per-token p50/p95/p99: "
        f"{metrics['per_token_s']['p50']*1e3:.2f}/"
        f"{metrics['per_token_s']['p95']*1e3:.2f}/"
        f"{metrics['per_token_s']['p99']*1e3:.2f} ms")
    log(f"prefix_hit_rate {metrics['prefix_hit_rate']:.2f} "
        f"(hit tokens {st['prefix_hit_tokens']}/{prompt_tokens}); "
        f"pages_shared {st['pages_shared']}; cow_copies {st['cow_copies']}; "
        f"evictions {st['evictions']}; page_stalls {st['page_stalls']}")
    if st["spec_steps"]:
        hist = engine.stats["accepted_hist"]
        log(f"speculative: {metrics['acceptance_rate']:.2f} acceptance "
            f"({st['draft_accepted']}/{st['draft_proposed']} drafts), "
            f"{metrics['decode_tokens_per_step']:.2f} committed "
            f"tokens/decode step; accepted-length histogram {hist}")
    return metrics


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    ``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself, wins when set;
    otherwise the cache lives in ``.jax_cache/`` at the root of the
    checkout. The path must not move between runs (it is part of what a
    cached entry is found by), so it is never a temporary, pid- or
    time-derived directory. Call before the first compile; returns the
    directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def select_backend(impl: Optional[str], page_size: int
                   ) -> Tuple[str, Optional[str]]:
    """``(kernel_impl, attn_impl)`` for this platform — the one place the
    serving entry points choose kernels.

    No ``impl`` → :func:`repro.kernels.ops.default_impl`: the compiled
    Pallas kernels on a TPU, the pure-JAX references elsewhere. With a
    paged pool (``page_size > 0``) the paged attention kernel follows the
    BCR kernels ("paged" / "paged_interpret"); ``attn_impl`` None keeps the
    config's own (the pure-JAX attention paths). ``interpret`` on a TPU and
    ``pallas`` off one are errors, not silent fallbacks."""
    platform = jax.default_backend()
    impl = impl or default_impl()
    if impl == "interpret" and platform == "tpu":
        raise ValueError("--impl interpret runs the kernels in the Pallas "
                         "interpreter; on a TPU use pallas (or ref as the "
                         "oracle)")
    if impl == "pallas" and platform != "tpu":
        raise ValueError(f"--impl pallas needs a TPU backend, found "
                         f"{platform!r} (use interpret or ref)")
    attn = {"pallas": "paged", "interpret": "paged_interpret"}.get(impl)
    return impl, (attn if page_size else None)


def build_config(args) -> ModelConfig:
    """Model config for the serving CLIs' shared flags (``arch``,
    ``smoke``, ``bcr_keep``, ``bcr_block``, ``impl``, ``page_size``), with
    the kernel backend from :func:`select_backend`."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    kernel_impl, attn_impl = select_backend(args.impl, args.page_size)
    cfg = dataclasses.replace(cfg, bcr_keep_frac=args.bcr_keep,
                              kernel_impl=kernel_impl)
    if attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    if args.bcr_block or args.smoke:
        b = args.bcr_block or 16
        cfg = dataclasses.replace(cfg, bcr_block=(b, b))
    return cfg


def build_engine(args, log=print, **engine_kw) -> InferenceEngine:
    """Config, random-init (BCR-packed) params, optional drafter and the
    engine, from the flags ``launch/serve.py`` and ``launch/api.py`` share.
    ``engine_kw`` adds EngineConfig fields only one entry point has.
    Raises ValueError for flag combinations the engine cannot serve."""
    if args.prefix_cache and not args.page_size:
        raise ValueError("--prefix-cache needs --page-size (paged KV pool)")
    if args.spec_k and not args.page_size:
        raise ValueError("--spec-k needs --page-size (verification runs "
                         "through the paged prefill-append kernel)")
    cfg = build_config(args)
    params = build_params(
        cfg, log, decode_m=args.slots,
        auto_block=getattr(args, "auto_block", False),
        plan_fitness=getattr(args, "plan_fitness", "analytic"),
        weight_dtype=args.weight_dtype)
    draft_cfg, draft_params = None, None
    if args.spec_k:
        draft_cfg, draft_params = build_draft(cfg, args, log=log)
    return InferenceEngine(cfg, params, EngineConfig(
        n_slots=args.slots, capacity=args.capacity,
        page_size=args.page_size, kv_pages=args.kv_pages or None,
        prefix_cache=args.prefix_cache,
        spec_k=args.spec_k, draft_cfg=draft_cfg,
        kv_dtype=args.kv_dtype,
        max_waiting=args.max_waiting or None,
        preempt_after_stalls=args.preempt_after_stalls,
        slo_admission=args.slo_admission, slo_slack=args.slo_slack,
        mesh_model=args.mesh_model, **engine_kw),
        draft_params=draft_params)


def build_params(cfg: ModelConfig, log=print, *, decode_m: int = 8,
                 auto_block: bool = False,
                 plan_fitness: str = "analytic",
                 weight_dtype: str = "") -> PyTree:
    fns = model_fns(cfg)
    params = fns.init_params(jax.random.PRNGKey(0))
    if cfg.bcr_keep_frac > 0:
        # tune the execution plans for the batch this server will decode
        # at (the engine's plan_params preserves pre-tuned plans)
        packed = pack_params(cfg, params, decode_m=decode_m,
                             auto_block=auto_block,
                             plan_fitness=plan_fitness,
                             weight_dtype=weight_dtype)
        log(f"packed weight bytes: "
            f"{packed_fraction(params, packed):.3f}x dense")
        params = packed
    return params


def build_draft(cfg: ModelConfig, args, log=print):
    """Drafter for --spec-k: the same ``causal_lm`` stack at a fraction of
    the target's width/depth, sharing its vocab and head counts (so
    head_dim stays integral) and forced pure-attention (recurrent mixers
    / MoE routing cannot rewind on a rejected draft). Random-init, like
    everything else this synthetic-weights CLI serves; --draft-bcr-keep
    packs it so the drafter itself decodes off the BCR format."""
    dm = args.draft_d_model or cfg.d_model // 4
    # round to the head count so head_dim = dm // num_heads stays ≥ 1 and
    # exact — an unrounded --draft-d-model would otherwise fail with a
    # shape error deep inside the drafter's init
    dm = max(cfg.num_heads, dm // cfg.num_heads * cfg.num_heads)
    if args.draft_d_model and dm != args.draft_d_model:
        log(f"--draft-d-model {args.draft_d_model} rounded to {dm} "
            f"({cfg.num_heads} heads)")
    draft_cfg = dataclasses.replace(
        cfg, name=cfg.name + "-draft", num_layers=args.draft_layers,
        d_model=dm, head_dim=dm // cfg.num_heads, d_ff=max(8, dm * 2),
        num_experts=0, attn_period=0,
        bcr_keep_frac=args.draft_bcr_keep)
    dparams = model_fns(draft_cfg).init_params(jax.random.PRNGKey(1))
    if args.draft_bcr_keep > 0:
        dparams = pack_params(draft_cfg, dparams, decode_m=args.slots)
    log(f"drafter: {args.draft_layers}L d_model={dm} "
        f"(keep_frac={args.draft_bcr_keep})")
    return draft_cfg, dparams


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--mode", default="traffic", choices=["traffic", "static"])
    p.add_argument("--batch", type=int, default=4, help="static-mode batch")
    p.add_argument("--slots", type=int, default=8, help="engine decode slots")
    p.add_argument("--capacity", type=int, default=128)
    p.add_argument("--page-size", type=int, default=0,
                   help="block-paged KV page size (tokens); 0 → capacity-"
                        "dense slots")
    p.add_argument("--kv-pages", type=int, default=0,
                   help="total KV pages per layer (0 → full provisioning); "
                        "< slots×capacity/page oversubscribes HBM with "
                        "page-budget admission control")
    p.add_argument("--prefix-cache", action="store_true",
                   help="share KV pages across requests: admissions adopt "
                        "cached full-page prompt prefixes (ref-counted, "
                        "CoW) and prefill only the uncached suffix "
                        "(needs --page-size)")
    p.add_argument("--system-prompts", type=int, default=0,
                   help="shared-prefix workload: N fixed system prompts; "
                        "each request = one of them + a random user "
                        "suffix (0 → fully random prompts)")
    p.add_argument("--system-len", type=int, default=32,
                   help="system-prompt length (tokens) for "
                        "--system-prompts")
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative decoding: a small drafter proposes "
                        "up to k tokens per slot and ONE prefill_append "
                        "dispatch verifies them all (needs --page-size; "
                        "0 → plain decode)")
    p.add_argument("--draft-d-model", type=int, default=0,
                   help="drafter width (0 → target d_model // 4, rounded "
                        "to the head count)")
    p.add_argument("--draft-layers", type=int, default=2,
                   help="drafter depth")
    p.add_argument("--draft-bcr-keep", type=float, default=0.0,
                   help="BCR-pack the drafter at this keep fraction "
                        "(0 → dense drafter)")
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--rate", type=float, default=8.0, help="req/s (Poisson)")
    p.add_argument("--trace", default=None, metavar="FILE.jsonl",
                   help="replay a jsonl request trace instead of Poisson "
                        "arrivals: per-record arrival offset 't', "
                        "prompt_len, max_new_tokens, priority, deadline_s "
                        "(see examples/trace_heavy_tail.jsonl)")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--deadline-s", type=float, default=0.0,
                   help="per-request deadline (seconds from submit): past "
                        "it requests retire as TIMEOUT, waiting or "
                        "mid-decode (0 → no deadlines)")
    p.add_argument("--cancel-rate", type=float, default=0.0,
                   help="fraction of requests cancelled client-side at a "
                        "random point after arrival (0 → none)")
    p.add_argument("--max-waiting", type=int, default=0,
                   help="bound the waiting queue: beyond it submit sheds "
                        "the earliest-deadline waiting request as REJECTED "
                        "(0 → unbounded)")
    p.add_argument("--slo-admission", action="store_true",
                   help="SLO-aware admission: reject a deadline-carrying "
                        "request at submit when the seat-time estimator "
                        "(occupancy + queue + step-time EWMA + prefix-"
                        "cache probe) says it cannot finish in time")
    p.add_argument("--slo-slack", type=float, default=1.0,
                   help="admission slack: admit while estimated finish ≤ "
                        "slack × deadline (>1 lenient, <1 conservative)")
    p.add_argument("--tenants", default="",
                   help="comma-separated tenant names; Poisson-mode "
                        "requests are tagged round-robin (enables the "
                        "per-tenant metrics breakdown)")
    p.add_argument("--preempt-after-stalls", type=int, default=0,
                   help="page-pressure preemption: after this many "
                        "consecutive fully-stalled admission steps, evict "
                        "the youngest running slot (0 → off)")
    p.add_argument("--bcr-keep", type=float, default=0.0)
    p.add_argument("--bcr-block", type=int, default=0,
                   help="BCR block side; 0 → 16 for --smoke configs "
                        "(whose d_model is too small for the 128 default), "
                        "else the config default")
    p.add_argument("--impl", default=None,
                   choices=["ref", "interpret", "pallas"],
                   help="kernel backend (default: pallas on a TPU, ref "
                        "elsewhere; ref is the pure-JAX oracle, interpret "
                        "is CPU-only)")
    p.add_argument("--auto-block", action="store_true",
                   help="Listing-1 block-size search per layer geometry at "
                        "pack time instead of taking the config block")
    p.add_argument("--plan-fitness", default="analytic",
                   choices=["analytic", "wallclock"],
                   help="GA plan-tuner fitness backend (wallclock times "
                        "the jitted matmul per genome on this host)")
    p.add_argument("--kv-dtype", default="", choices=["", "int8"],
                   help="int8: store attention KV as symmetric int8 codes "
                        "+ per-row-per-head fp32 scales, dequantized "
                        "inside the paged Pallas kernels (~0.53x KV bytes "
                        "per decode step vs bf16 pools)")
    p.add_argument("--weight-dtype", default="", choices=["", "int8"],
                   help="int8: quantize packed BCR tiles to int8 codes + "
                        "per-block scales applied in the kernel epilogue "
                        "(halves packed weight bytes; needs --bcr-keep)")
    p.add_argument("--mesh-model", type=int, default=1,
                   help="tensor-parallel mesh size: shard every engine "
                        "program over this many devices (projections "
                        "column-parallel, KV pool head-parallel; greedy "
                        "tokens stay bit-identical to --mesh-model 1). "
                        "Needs --page-size on a dense/vlm arch whose head "
                        "counts divide the mesh. CPU testing: set "
                        "XLA_FLAGS=--xla_force_host_platform_device_count")
    p.add_argument("--json-out", default=None)
    args = p.parse_args()
    enable_compile_cache()

    if args.mode == "static":
        try:
            cfg = build_config(args)
        except ValueError as e:
            p.error(str(e))
        params = build_params(cfg, decode_m=args.batch,
                              auto_block=args.auto_block,
                              plan_fitness=args.plan_fitness,
                              weight_dtype=args.weight_dtype)
        if args.kv_dtype:
            cfg = dataclasses.replace(cfg, kv_dtype=args.kv_dtype)
        generate(cfg, params, ServeConfig(batch=args.batch,
                                          prompt_len=args.prompt_len,
                                          gen_tokens=args.gen,
                                          capacity=args.capacity))
        return

    try:
        engine = build_engine(args)
    except ValueError as e:
        p.error(str(e))
    # mixed prompt lengths around --prompt-len, clamped so every request
    # fits its slot (prompt + gen + spec headroom ≤ capacity;
    # shared-prefix workloads also carry --system-len tokens per prompt)
    pmax = args.capacity - args.gen - args.spec_k - (
        args.system_len if args.system_prompts else 0)
    if pmax < 1:
        p.error(f"--capacity {args.capacity} leaves no room for prompts "
                f"after --gen {args.gen}"
                + (f" + --system-len {args.system_len}"
                   if args.system_prompts else ""))
    plens = {max(4, args.prompt_len // 2), args.prompt_len,
             args.prompt_len * 2}
    plens = tuple(sorted(min(max(x, 1), pmax) for x in plens))
    tc = TrafficConfig(
        n_requests=args.requests, rate=args.rate, gen_tokens=args.gen,
        prompt_lens=plens,
        temperature=args.temperature, top_k=args.top_k,
        system_prompts=args.system_prompts, system_len=args.system_len,
        deadline_s=args.deadline_s, cancel_rate=args.cancel_rate,
        trace=load_trace(args.trace) if args.trace else None,
        tenants=tuple(t for t in args.tenants.split(",") if t))
    metrics = run_traffic(engine, tc)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(metrics, f, indent=2)


if __name__ == "__main__":
    main()
