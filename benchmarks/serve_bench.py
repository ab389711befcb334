"""Serving benchmark: continuous-batching engine vs the static one-batch
loop, across slot counts and BCR keep fractions. Emits BENCH_serve.json.

At equal offered load (same request set), the engine's win comes from slot
reuse: the static loop decodes one fixed batch to the longest request's
completion before admitting the next batch, while the engine backfills
freed slots immediately, so the padded decode batch stays full.

    PYTHONPATH=src python benchmarks/serve_bench.py --arch llama3.2-1b \
        --slots 4 8 --keeps 0 0.25 --requests 16 --gen 16

``--long-context`` adds the block-paged KV section: at capacity ≥ 2048
with mixed mostly-short prompts, the paged engine (page pool + block
tables + length-aware decode) is measured against the masked-dense engine
at matched occupancy, with per-step KV bytes-read accounting for both
(`paged_vs_masked` / `long_context` in the JSON; ``--min-paged-vs-masked``
turns the ratio into a CI gate).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.serve import ServeConfig, generate, pack_params
from repro.models.api import model_fns
from repro.serving import EngineConfig, InferenceEngine, TenantQuota


def scaled_cfg(args, keep):
    """The sweep's serving-scale smoke config (shared with the long-context
    section so both measure the same model body): d_model/d_ff/layers
    overrides until the decode step is weight-bound."""
    cfg = get_smoke_config(args.arch)
    over = {"bcr_keep_frac": keep,
            "bcr_block": (args.bcr_block, args.bcr_block)}
    if args.d_model:
        over.update(d_model=args.d_model,
                    head_dim=args.d_model // cfg.num_heads)
    if args.d_ff:
        over["d_ff"] = args.d_ff
    if args.layers:
        over["num_layers"] = args.layers
    return dataclasses.replace(cfg, **over)


def make_requests(cfg, n, prompt_lens, gen_max, seed=0):
    """Mixed load: per-request prompt length AND generation length (real
    traffic never finishes in lockstep — that raggedness is exactly what
    continuous batching exploits)."""
    rng = np.random.default_rng(seed)
    plens = rng.choice(prompt_lens, size=n)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(p)).astype(np.int32)
               for p in plens]
    gens = rng.integers(max(1, gen_max // 4), gen_max + 1, size=n).tolist()
    return prompts, gens


def bench_engine(cfg, params, prompts, gens, n_slots, capacity,
                 page_size=0):
    eng = InferenceEngine(cfg, params,
                          EngineConfig(n_slots=n_slots, capacity=capacity,
                                       page_size=page_size))
    # jit compiles (prefill buckets, decode — incl. every paged
    # block-table width — and sample) stay outside the timed window;
    # warmup() wipes the bookkeeping afterwards
    eng.warmup([len(p) for p in prompts])
    t0 = time.perf_counter()
    rids = [eng.submit(p, max_new_tokens=g) for p, g in zip(prompts, gens)]
    done = {r.rid: r for r in eng.run()}
    dt = time.perf_counter() - t0
    toks = sum(len(done[r].generated) for r in rids)
    occ = eng.stats["slot_occupancy"]
    steps = max(eng.stats["decode_steps"], 1)
    return {"tok_s": toks / dt, "elapsed_s": dt, "tokens": toks,
            "decode_steps": eng.stats["decode_steps"],
            "mean_occupancy": float(np.mean(occ)) if occ else 0.0,
            # KV traffic accounting: what the decode dispatch reads
            # (masked-dense → B×capacity; paged → B×live-bucket) and the
            # per-slot live-page floor the Pallas kernel achieves
            "kv_bytes_per_step": eng.stats["kv_bytes_read"] / steps,
            "kv_bytes_per_step_live": (eng.stats["kv_bytes_read_live"]
                                       / steps)}


def bench_long_context(args):
    """Capacity-dominated regime (capacity ≥ 2048, mixed mostly-short
    prompts): masked-dense decode pays the full provisioned cache every
    step, paged decode pays the live bucket. Dense weights on purpose —
    this isolates the KV-traffic lever from the weight-format lever the
    main sweep measures."""
    cap = args.long_capacity
    cfg = scaled_cfg(args, keep=0.0)
    fns = model_fns(cfg)
    params = fns.init_params(jax.random.PRNGKey(0))
    prompts, gens = make_requests(cfg, args.long_requests,
                                  args.long_prompt_lens, args.long_gen,
                                  seed=1)
    n_slots = max(args.slots)
    masked = bench_engine(cfg, params, prompts, gens, n_slots, cap)
    paged = bench_engine(cfg, params, prompts, gens, n_slots, cap,
                         page_size=args.page_size)
    row = {
        "section": "long_context", "arch": args.arch, "batch": n_slots,
        "capacity": cap, "page_size": args.page_size,
        "prompt_lens": list(args.long_prompt_lens),
        "d_model": cfg.d_model,
        "paged": paged, "masked": masked,
        "paged_vs_masked": paged["tok_s"] / masked["tok_s"],
        "kv_bytes_capacity_ratio": (paged["kv_bytes_per_step"]
                                    / masked["kv_bytes_per_step"]),
    }
    print(f"long-context cap={cap} batch={n_slots}: paged "
          f"{paged['tok_s']:.1f} tok/s vs masked-dense "
          f"{masked['tok_s']:.1f} tok/s → {row['paged_vs_masked']:.2f}x; "
          f"KV bytes/step {paged['kv_bytes_per_step']/1e3:.0f}K (live "
          f"{paged['kv_bytes_per_step_live']/1e3:.0f}K) vs "
          f"{masked['kv_bytes_per_step']/1e3:.0f}K "
          f"({row['kv_bytes_capacity_ratio']:.2f}x of capacity reads)")
    return row


def bench_shared_prefix(args):
    """Prefix-cache payoff at batch 8: TTFT of a prefix-hit admission
    (suffix-only prefill over adopted pages) vs a cold prefill of the full
    prompt, plus total pages allocated vs the unshared paged engine on the
    SAME workload (dense weights — isolates the sharing lever).

    Workload: two admission waves of `batch` requests, every prompt =
    one shared system prompt (`--system-len`) + a short per-request user
    suffix. Wave 1 is cold and publishes the system pages; wave 2 hits.
    TTFT is measured per request from submit to the recorded first-token
    time (the decode step after admission is excluded), with all programs
    precompiled by warmup."""
    sfx_lens = list(args.sfx_lens)
    cap = args.system_len + max(sfx_lens) + args.long_gen + args.page_size
    cfg = scaled_cfg(args, keep=0.0)
    fns = model_fns(cfg)
    params = fns.init_params(jax.random.PRNGKey(0))
    batch = max(args.slots)
    rng = np.random.default_rng(7)
    system = rng.integers(0, cfg.vocab_size,
                          size=args.system_len).astype(np.int32)

    def wave(seed):
        r = np.random.default_rng(seed)
        return [np.concatenate([system, r.integers(
            0, cfg.vocab_size,
            size=int(sfx_lens[i % len(sfx_lens)])).astype(np.int32)])
            for i in range(batch)]

    def admit_ttft(eng, prompts):
        """Submit a full batch, run the admission step, read per-request
        TTFT off the engine's own first-token timestamps."""
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=args.long_gen)
                for p in prompts]
        eng.step()
        reqs = {r.rid: r for r in list(eng.sched.active.values())
                + eng.sched.finished}
        ttft = [reqs[rid].first_token_time - t0 for rid in rids]
        done = {r.rid: r.generated for r in eng.run()}
        return float(np.mean(ttft)), [done[rid] for rid in rids]

    waves = [wave(11), wave(12)]
    results = {}
    for shared in (True, False):
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=batch, capacity=cap, page_size=args.page_size,
            prefix_cache=shared))
        eng.warmup([len(p) for p in waves[0]],
                   suffix_lens=[max(sfx_lens) + args.page_size, 1])
        t_cold, toks_cold = admit_ttft(eng, waves[0])
        t_second, toks_second = admit_ttft(eng, waves[1])
        results[shared] = dict(
            ttft_cold=t_cold, ttft_second=t_second,
            tokens=toks_cold + toks_second,
            pages_allocated=eng.stats["pages_allocated"],
            prefix_hit_tokens=eng.stats["prefix_hit_tokens"],
            pages_shared=eng.stats["pages_shared"],
            cow_copies=eng.stats["cow_copies"])
    assert results[True]["tokens"] == results[False]["tokens"], \
        "prefix sharing changed generated tokens"
    sh, un = results[True], results[False]
    row = {
        "section": "shared_prefix", "arch": args.arch, "batch": batch,
        "system_len": args.system_len, "sfx_lens": sfx_lens,
        "page_size": args.page_size, "capacity": cap,
        "d_model": cfg.d_model,
        "ttft_cold_s": sh["ttft_cold"], "ttft_hit_s": sh["ttft_second"],
        "prefix_ttft_speedup": sh["ttft_cold"] / sh["ttft_second"],
        "prefix_hit_tokens": sh["prefix_hit_tokens"],
        "pages_shared": sh["pages_shared"],
        "cow_copies": sh["cow_copies"],
        "pages_allocated": sh["pages_allocated"],
        "pages_allocated_unshared": un["pages_allocated"],
        "tokens_match_unshared": True,
    }
    print(f"shared-prefix batch={batch} sys={args.system_len}: hit TTFT "
          f"{sh['ttft_second']*1e3:.1f} ms vs cold "
          f"{sh['ttft_cold']*1e3:.1f} ms → "
          f"{row['prefix_ttft_speedup']:.2f}x; pages allocated "
          f"{sh['pages_allocated']} vs {un['pages_allocated']} unshared "
          f"({sh['pages_shared']} adopted, {sh['cow_copies']} CoW)")
    return row


def bench_speculative(args):
    """Speculative-decode payoff at batch 8 (dense weights, paged KV —
    isolates the verify-dispatch lever from the weight-format lever):
    plain paged decode vs draft→verify→accept with two drafters.

    (a) The high-acceptance oracle (``serving/speculative.OracleDraft``)
    replays the plain run's own greedy continuations, so every draft is
    accepted and each step commits ``spec_k + 1`` tokens for ONE
    ``prefill_append`` verify dispatch — this measures the economics the
    gate cares about: a k+1-row verify costs far less than k+1 sequential
    decode dispatches in the weight-bound regime. (b) A small real
    ``DraftModel`` (the unscaled smoke config, random weights → near-zero
    acceptance) bounds the overhead floor; reported, not gated. Both runs
    must emit bit-identical tokens to the plain run — acceptance
    re-derives every token from the target's logits."""
    from repro.serving.speculative import OracleDraft

    cfg = scaled_cfg(args, keep=0.0)
    fns = model_fns(cfg)
    params = fns.init_params(jax.random.PRNGKey(0))
    batch = max(args.slots)
    prompts, gens = make_requests(cfg, args.requests, args.prompt_lens,
                                  args.gen, seed=3)

    def run(spec_k=0, drafter=None, draft_cfg=None, draft_params=None,
            ref_tokens=None):
        """Warm up once, then time ``--spec-iters`` submit+drain passes of
        the same workload and keep the best — the drained runs are short
        (a handful of engine steps), so a single scheduler hiccup on a
        shared CI box would otherwise dominate the gated ratio."""
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=batch, capacity=args.capacity,
            page_size=args.page_size, spec_k=spec_k, draft_cfg=draft_cfg),
            draft_params=draft_params, drafter=drafter)
        eng.warmup([len(p) for p in prompts])
        best, toks = None, None
        for _ in range(max(1, args.spec_iters)):
            eng.reset_stats()
            t0 = time.perf_counter()
            rids = [eng.submit(p, max_new_tokens=g)
                    for p, g in zip(prompts, gens)]
            if isinstance(drafter, OracleDraft):
                # the oracle replays the plain run's tokens, keyed by the
                # live rids (warmup and earlier passes consumed id space)
                drafter.continuations = dict(zip(rids, ref_tokens))
            done = {r.rid: r for r in eng.run()}
            dt = time.perf_counter() - t0
            out = [done[r].generated for r in rids]
            assert toks is None or out == toks, \
                "repeated passes diverged on identical greedy input"
            toks = out
            st = eng.stats
            row = {"tok_s": sum(len(t) for t in out) / dt,
                   "elapsed_s": dt,
                   "decode_steps": st["decode_steps"],
                   "draft_proposed": st["draft_proposed"],
                   "draft_accepted": st["draft_accepted"],
                   "acceptance_rate": (st["draft_accepted"]
                                       / max(st["draft_proposed"], 1))}
            if best is None or row["tok_s"] > best["tok_s"]:
                best = row
        return best, toks

    plain, ref_toks = run()
    oracle_row, oracle_toks = run(spec_k=args.spec_k, drafter=OracleDraft(),
                                  ref_tokens=ref_toks)
    assert oracle_toks == ref_toks, \
        "speculative greedy decode changed the generated tokens"
    draft_cfg = dataclasses.replace(
        get_smoke_config(args.arch),
        bcr_block=(args.bcr_block, args.bcr_block))
    draft_params = model_fns(draft_cfg).init_params(jax.random.PRNGKey(1))
    model_row, model_toks = run(spec_k=args.spec_k, draft_cfg=draft_cfg,
                                draft_params=draft_params)
    assert model_toks == ref_toks, \
        "speculative greedy decode changed the generated tokens"
    row = {
        "section": "speculative", "arch": args.arch, "batch": batch,
        "spec_k": args.spec_k, "capacity": args.capacity,
        "page_size": args.page_size, "d_model": cfg.d_model,
        "draft_d_model": draft_cfg.d_model,
        "plain": plain, "oracle": oracle_row, "model_draft": model_row,
        "spec_vs_plain": oracle_row["tok_s"] / plain["tok_s"],
        "model_draft_vs_plain": model_row["tok_s"] / plain["tok_s"],
        "tokens_match_plain": True,
    }
    print(f"speculative k={args.spec_k} batch={batch}: oracle "
          f"{oracle_row['tok_s']:.1f} tok/s "
          f"(acceptance {oracle_row['acceptance_rate']:.2f}, "
          f"{oracle_row['decode_steps']} steps) vs plain "
          f"{plain['tok_s']:.1f} tok/s ({plain['decode_steps']} steps) → "
          f"{row['spec_vs_plain']:.2f}x; real drafter "
          f"(d_model {draft_cfg.d_model}) {model_row['tok_s']:.1f} tok/s, "
          f"acceptance {model_row['acceptance_rate']:.2f}")
    return row


def _divergence_rate(ref, alt):
    """Greedy-divergence rate between two sets of token sequences: once a
    sequence diverges, EVERY token from the first mismatch counts as
    diverged (a changed token reshapes the whole continuation, so
    per-position agreement past it would flatter the metric)."""
    div = tot = 0
    for a, b in zip(ref, alt):
        n = max(len(a), len(b))
        tot += n
        first = next((i for i in range(min(len(a), len(b)))
                      if a[i] != b[i]), None)
        if first is None and len(a) != len(b):
            first = min(len(a), len(b))
        if first is not None:
            div += n - first
    return div / max(tot, 1)


def _forced_argmax(cfg, params, prompts, seqs, capacity):
    """Greedy argmax at every decode position, teacher-forced on ``seqs``
    (the fp engine's trajectory): prefill the prompt, then feed the fp
    tokens one at a time and record what THIS model would have picked.
    Because the context is pinned to the fp trajectory, a flip at step t
    does not contaminate step t+1 — the per-position flip rate measures
    quantization's effect on the greedy decision itself, not the
    avalanche a single early flip sets off in free-running decode."""
    from repro.serving.kv_slots import seat_prefill
    fns = model_fns(cfg)
    prefill = jax.jit(fns.prefill)
    step = jax.jit(fns.decode_step)
    out = []
    for prompt, gen in zip(prompts, seqs):
        if not len(gen):
            out.append([])
            continue
        toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
        logits, pc = prefill(params, {"tokens": toks})
        cache = seat_prefill(fns.init_cache, pc, 1, capacity)
        picks = [int(jnp.argmax(logits[0, -1]))]
        clen = len(prompt)
        for t in gen[:-1]:
            logits, cache = step(
                params, {"tokens": jnp.asarray([[t]], jnp.int32),
                         "cache_len": jnp.asarray([clen], jnp.int32)},
                cache)
            clen += 1
            picks.append(int(jnp.argmax(logits[0, -1])))
        out.append(picks)
    return out


def _flip_rate(a_seqs, b_seqs):
    flips = tot = 0
    for a, b in zip(a_seqs, b_seqs):
        tot += len(a)
        flips += sum(x != y for x, y in zip(a, b))
    return flips / max(tot, 1)


def bench_quantized(args):
    """Quantized-serving payoff at batch 8: the fp paged engine vs the
    same engine with int8 KV pages (+ per-row scales, dequantized in the
    kernels), and — when a packed keep is benched — fp vs int8 packed BCR
    weights. Reports per-step KV bytes, tok/s, the resident-tokens-per-
    page-budget ratio (straight from the two pools' actual bytes per KV
    row) and quality metrics vs the fp run: the free-running greedy
    divergence rate (first mismatch condemns the tail — pessimistic on a
    random-weight smoke model whose near-tied logits avalanche) and
    teacher-forced per-decision flip rates vs the fp32-cache oracle for
    both int8 and the shipped bf16 baseline; CI gates the EXCESS rate
    (int8 − bf16)."""
    cfg = scaled_cfg(args, keep=0.0)
    fns = model_fns(cfg)
    params = fns.init_params(jax.random.PRNGKey(0))
    batch = max(args.slots)
    prompts, gens = make_requests(cfg, args.requests, args.prompt_lens,
                                  args.gen, seed=5)

    def run(params_, kv_dtype=""):
        """Best-of-N submit+drain passes (same de-noising rationale as the
        speculative bench); token sequences must repeat exactly."""
        eng = InferenceEngine(cfg, params_, EngineConfig(
            n_slots=batch, capacity=args.capacity,
            page_size=args.page_size, kv_dtype=kv_dtype))
        eng.warmup([len(p) for p in prompts])
        best, toks = None, None
        for _ in range(max(1, args.spec_iters)):
            eng.reset_stats()
            t0 = time.perf_counter()
            rids = [eng.submit(p, max_new_tokens=g)
                    for p, g in zip(prompts, gens)]
            done = {r.rid: r for r in eng.run()}
            dt = time.perf_counter() - t0
            out = [done[r].generated for r in rids]
            assert toks is None or out == toks, \
                "repeated passes diverged on identical greedy input"
            toks = out
            steps = max(eng.stats["decode_steps"], 1)
            row = {"tok_s": sum(len(t) for t in out) / dt,
                   "elapsed_s": dt,
                   "decode_steps": eng.stats["decode_steps"],
                   "kv_bytes_per_step": (eng.stats["kv_bytes_read"]
                                         / steps),
                   "kv_bytes_per_step_live": (
                       eng.stats["kv_bytes_read_live"] / steps),
                   "kv_row_bytes": eng._kv_row_bytes}
            if best is None or row["tok_s"] > best["tok_s"]:
                best = row
        return best, toks

    fp, fp_toks = run(params)
    q, q_toks = run(params, kv_dtype="int8")
    row = {
        "section": "quantized", "arch": args.arch, "batch": batch,
        "capacity": args.capacity, "page_size": args.page_size,
        "d_model": cfg.d_model,
        "fp": fp, "int8_kv": q,
        "kv_bytes_ratio": (q["kv_bytes_per_step"]
                           / fp["kv_bytes_per_step"]),
        "quant_vs_fp": q["tok_s"] / fp["tok_s"],
        # tokens a fixed page budget keeps resident, int8 vs fp — from
        # the pools' ACTUAL per-position bytes (codes + scale leaves)
        "resident_tokens_ratio": fp["kv_row_bytes"] / q["kv_row_bytes"],
        "divergence_rate": _divergence_rate(fp_toks, q_toks),
    }
    # teacher-forced flip rates: every cache format replays the same fp
    # greedy trajectory so a single early flip doesn't count every
    # subsequent token, and each is scored against the fp32-cache oracle.
    # The bf16 baseline cache flips near-tied argmaxes on its own (the
    # smoke model's random logits sit near ties far more often than a
    # trained model's), so the gated number is the EXCESS rate — flips
    # int8 adds beyond what the shipped bf16 cache already costs. Probe
    # trajectories run to the capacity limit: the timed CI workload
    # yields only ~130 greedy decisions, a coin toss for a 2% gate.
    probe_gen = max(args.gen, args.capacity - max(len(p) for p in prompts))
    eng = InferenceEngine(cfg, params, EngineConfig(
        n_slots=batch, capacity=args.capacity, page_size=args.page_size))
    probe = eng.generate(prompts, max_new_tokens=probe_gen)
    oracle = _forced_argmax(dataclasses.replace(cfg, cache_dtype="float32"),
                            params, prompts, probe, args.capacity)
    base_picks = _forced_argmax(cfg, params, prompts, probe, args.capacity)
    q_picks = _forced_argmax(dataclasses.replace(cfg, kv_dtype="int8"),
                             params, prompts, probe, args.capacity)
    row["forced_flip_rate"] = _flip_rate(oracle, q_picks)
    row["baseline_flip_rate"] = _flip_rate(oracle, base_picks)
    row["excess_flip_rate"] = max(
        0.0, row["forced_flip_rate"] - row["baseline_flip_rate"])
    row["forced_flip_tokens"] = sum(len(p) for p in probe)
    print(f"quantized batch={batch}: int8 KV {q['tok_s']:.1f} tok/s vs fp "
          f"{fp['tok_s']:.1f} tok/s → {row['quant_vs_fp']:.2f}x; KV "
          f"bytes/step {q['kv_bytes_per_step']/1e3:.0f}K vs "
          f"{fp['kv_bytes_per_step']/1e3:.0f}K "
          f"({row['kv_bytes_ratio']:.3f}x); resident tokens "
          f"{row['resident_tokens_ratio']:.2f}x per page budget; greedy "
          f"divergence {row['divergence_rate']:.4f} free-running; "
          f"teacher-forced flips vs fp32 oracle: int8 "
          f"{row['forced_flip_rate']:.4f}, bf16 baseline "
          f"{row['baseline_flip_rate']:.4f} → excess "
          f"{row['excess_flip_rate']:.4f} "
          f"({row['forced_flip_tokens']} decisions)")

    keep = max(args.keeps)
    if keep > 0:
        # int8 packed BCR weights vs fp packed, same workload (KV fp both
        # sides — isolates the weight-format lever)
        pcfg = scaled_cfg(args, keep)
        pparams = model_fns(pcfg).init_params(jax.random.PRNGKey(0))
        packed_fp = pack_params(pcfg, pparams)
        packed_q = pack_params(pcfg, pparams, weight_dtype="int8")
        from repro.launch.serve import packed_fraction
        wfp, wfp_toks = run(packed_fp)
        wq, wq_toks = run(packed_q)
        row.update(
            keep_frac=keep,
            weight_fp=wfp, weight_int8=wq,
            weight_int8_vs_fp=wq["tok_s"] / wfp["tok_s"],
            weight_bytes_ratio=(packed_fraction(pparams, packed_q)
                                / packed_fraction(pparams, packed_fp)),
            weight_divergence_rate=_divergence_rate(wfp_toks, wq_toks),
            # same shared probe trajectories: teacher forcing only needs a
            # common context, not one generated by either packed model
            weight_forced_flip_rate=_flip_rate(
                _forced_argmax(pcfg, packed_fp, prompts, probe,
                               args.capacity),
                _forced_argmax(pcfg, packed_q, prompts, probe,
                               args.capacity)))
        print(f"  int8 weights keep={keep}: {wq['tok_s']:.1f} tok/s vs fp "
              f"packed {wfp['tok_s']:.1f} → "
              f"{row['weight_int8_vs_fp']:.2f}x; packed bytes "
              f"{row['weight_bytes_ratio']:.3f}x; greedy divergence "
              f"{row['weight_divergence_rate']:.4f} free-running, "
              f"{row['weight_forced_flip_rate']:.4f} teacher-forced")
    return row


def bench_overload(args):
    """Open-loop overload: Poisson arrivals far above the service rate,
    load shedding ON (bounded waiting queue + per-request deadlines) vs
    OFF (unbounded queue, no deadlines). The CI claim: with shedding on,
    the p99 TTFT of requests that actually finish stays bounded — the
    on/off ratio is gated by --max-overload-p99-ratio — and the engine
    drains with zero leaked pages (check_conservation) despite the churn
    of sheds and timeouts. Runs the raw smoke config dense: overload is a
    queueing-behavior bench, not a kernel bench."""
    from repro.launch.serve import TrafficConfig, run_traffic
    cfg = get_smoke_config(args.arch)
    params = model_fns(cfg).init_params(jax.random.PRNGKey(0))

    def run(max_waiting, deadline_s):
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=args.overload_slots, capacity=args.capacity,
            page_size=args.page_size, plan_packed=False,
            max_waiting=max_waiting))
        tc = TrafficConfig(
            n_requests=args.overload_requests, rate=args.overload_rate,
            prompt_lens=(4, 8, 12), gen_tokens=args.overload_gen,
            deadline_s=deadline_s, seed=11)
        m = run_traffic(eng, tc, log=lambda *a: None)
        eng.check_conservation()    # zero leaked pages/slots or it raises
        return m

    shed = run(max_waiting=args.overload_max_waiting,
               deadline_s=args.overload_deadline)
    noshed = run(max_waiting=None, deadline_s=0.0)
    ratio = (shed["ttft_s"]["p99"] / noshed["ttft_s"]["p99"]
             if noshed["ttft_s"]["p99"] > 0 else 0.0)
    row = {
        "section": "overload", "arch": args.arch,
        "rate": args.overload_rate, "requests": args.overload_requests,
        "gen": args.overload_gen, "slots": args.overload_slots,
        "page_size": args.page_size, "capacity": args.capacity,
        "max_waiting": args.overload_max_waiting,
        "deadline_s": args.overload_deadline,
        "shed_on": shed, "shed_off": noshed,
        "overload_p99_ratio": ratio,
        "leaked_pages": 0,      # check_conservation() raised otherwise
    }
    sc_on, sc_off = shed["status_counts"], noshed["status_counts"]
    print(f"overload rate={args.overload_rate}/s x"
          f"{args.overload_requests} req, {args.overload_slots} slots: "
          f"shed-on p99 TTFT {shed['ttft_s']['p99']*1e3:.1f} ms "
          f"(finished {sc_on['finished']}, rejected {sc_on['rejected']}, "
          f"timeout {sc_on['timeout']}, goodput "
          f"{shed['goodput_tok_s']:.1f} tok/s) vs shed-off "
          f"{noshed['ttft_s']['p99']*1e3:.1f} ms "
          f"(finished {sc_off['finished']}) → ratio {ratio:.3f}")
    return row


def bench_overload_slo(args):
    """Predictive admission vs reactive deadline enforcement on the SAME
    overload workload. The reactive run admits everything into an
    unbounded queue and enforces deadlines after the fact — doomed
    requests are admitted, wait, and TIMEOUT in the waiting queue, and
    the finished tail stretches toward the deadline. The predictive run
    arms the seat-time estimator instead: provably-doomed requests are
    rejected at submit with a computed Retry-After, and anything admitted
    was estimated to finish within slack x deadline. The CI claims:
    (a) zero admitted-then-TIMEOUT-in-the-waiting-queue under predictive
    admission, and (b) the p99 TTFT of admitted requests is no worse
    than the reactive run's (--max-slo-p99-ratio — structurally true
    because the estimator stops admitting around slack x deadline of
    queue delay while the reactive queue fills right up to the
    deadline). Wasted prefill (prompt tokens spent on requests that
    never delivered) is reported for both sides — the cost predictive
    admission exists to avoid."""
    from repro.launch.serve import TrafficConfig, run_traffic
    cfg = get_smoke_config(args.arch)
    params = model_fns(cfg).init_params(jax.random.PRNGKey(0))
    plens = (4, 8, 12)

    def run(slo):
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=args.overload_slots, capacity=args.capacity,
            page_size=args.page_size, plan_packed=False,
            slo_admission=slo, slo_slack=args.slo_slack))
        # calibrate the step-time EWMA before the measured window:
        # warmup() wipes it, an uncalibrated estimator admits everything
        # (reactive degrade), and at 400/s the whole burst arrives before
        # the first real steps could teach it anything. A short priming
        # drain gives the estimator measured step times; its requests are
        # then scrubbed from the books so the traffic run starts clean
        # (_step_time survives reset_stats by design). The reactive run
        # is primed identically so the comparison shares one code path.
        eng.warmup(list(plens))
        rng = np.random.default_rng(3)
        eng.generate(
            [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32)
             for p in plens], max_new_tokens=args.overload_gen)
        eng.sched.finished.clear()
        eng.reset_stats()
        tc = TrafficConfig(
            n_requests=args.overload_requests, rate=args.overload_rate,
            prompt_lens=plens, gen_tokens=args.overload_gen,
            deadline_s=args.slo_deadline, seed=11, warmup=False)
        m = run_traffic(eng, tc, log=lambda *a: None)
        eng.check_conservation()    # zero leaked pages/slots or it raises
        return m

    reactive = run(False)
    m = run(True)
    ratio = (m["ttft_s"]["p99"] / reactive["ttft_s"]["p99"]
             if reactive["ttft_s"]["p99"] > 0 else 0.0)
    row = {
        "section": "overload_slo", "arch": args.arch,
        "rate": args.overload_rate, "requests": args.overload_requests,
        "gen": args.overload_gen, "slots": args.overload_slots,
        "deadline_s": args.slo_deadline, "slo_slack": args.slo_slack,
        "predictive": m, "reactive": reactive,
        "slo_p99_ratio": ratio,
        "slo_rejected": m["slo_rejected"],
        "timeouts_waiting": m["timeouts_waiting"],
        "reactive_timeouts_waiting": reactive["timeouts_waiting"],
        "wasted_prefill_tokens": m["wasted_prefill_tokens"],
        "reactive_wasted_prefill_tokens": reactive["wasted_prefill_tokens"],
        "leaked_pages": 0,          # check_conservation() raised otherwise
    }
    sc = m["status_counts"]
    print(f"overload-slo rate={args.overload_rate}/s x"
          f"{args.overload_requests} req: predictive p99 TTFT "
          f"{m['ttft_s']['p99']*1e3:.1f} ms (finished {sc['finished']}, "
          f"slo-rejected {m['slo_rejected']}, waiting timeouts "
          f"{m['timeouts_waiting']}, wasted prefill "
          f"{m['wasted_prefill_tokens']} tok) vs reactive "
          f"{reactive['ttft_s']['p99']*1e3:.1f} ms (waiting timeouts "
          f"{reactive['timeouts_waiting']}, wasted prefill "
          f"{reactive['wasted_prefill_tokens']} tok) → ratio {ratio:.3f}")
    return row


def bench_tenancy(args):
    """Tenant isolation under an aggressor: a victim tenant offering a
    modest, fully-serviceable load (solo goodput ≈ its fair-share
    entitlement — it asks for less than half the machine) shares the
    engine with an aggressor flooding at ~20x the victim's rate. Weighted
    fair queueing (equal weights) must keep the victim's deadline-bound
    goodput: the gate (--min-victim-goodput-frac) bounds contended victim
    goodput tokens as a fraction of the solo run's. Both runs replay the
    same victim trace; deadlines turn lost share into measurable loss
    (waiting-queue timeouts) instead of unbounded latency."""
    from repro.launch.serve import TrafficConfig, run_traffic
    cfg = get_smoke_config(args.arch)
    params = model_fns(cfg).init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(17)
    vic_t = np.cumsum(rng.exponential(
        1.0 / args.tenancy_victim_rate, size=args.tenancy_victim_requests))
    agg_t = np.cumsum(rng.exponential(
        1.0 / args.tenancy_aggressor_rate,
        size=args.tenancy_aggressor_requests))

    def recs(ts, tenant):
        return [{"t": float(t), "prompt_len": 8,
                 "max_new_tokens": args.tenancy_gen,
                 "deadline_s": args.tenancy_deadline, "tenant": tenant}
                for t in ts]

    solo_trace = recs(vic_t, "victim")
    contended_trace = sorted(solo_trace + recs(agg_t, "aggressor"),
                             key=lambda r: r["t"])

    def run(trace):
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=args.overload_slots, capacity=args.capacity,
            page_size=args.page_size, plan_packed=False,
            tenant_quotas={"victim": TenantQuota(weight=1.0),
                           "aggressor": TenantQuota(weight=1.0)}))
        tc = TrafficConfig(trace=trace, gen_tokens=args.tenancy_gen,
                           seed=17)
        m = run_traffic(eng, tc, log=lambda *a: None)
        eng.check_conservation()    # zero leaked pages/slots or it raises
        return m

    solo = run(solo_trace)
    cont = run(contended_trace)
    vic_solo = solo["tenants"].get("victim", {})
    vic_cont = cont["tenants"].get("victim", {})
    agg_cont = cont["tenants"].get("aggressor", {})
    frac = (vic_cont.get("goodput_tokens", 0)
            / max(vic_solo.get("goodput_tokens", 0), 1))
    row = {
        "section": "tenancy", "arch": args.arch,
        "slots": args.overload_slots, "capacity": args.capacity,
        "page_size": args.page_size, "gen": args.tenancy_gen,
        "deadline_s": args.tenancy_deadline,
        "victim_rate": args.tenancy_victim_rate,
        "victim_requests": args.tenancy_victim_requests,
        "aggressor_rate": args.tenancy_aggressor_rate,
        "aggressor_requests": args.tenancy_aggressor_requests,
        "victim_solo": vic_solo, "victim_contended": vic_cont,
        "aggressor_contended": agg_cont,
        "victim_goodput_frac": frac,
        "leaked_pages": 0,          # check_conservation() raised otherwise
    }
    print(f"tenancy victim {args.tenancy_victim_rate}/s vs aggressor "
          f"{args.tenancy_aggressor_rate}/s on {args.overload_slots} "
          f"slots: victim goodput {vic_cont.get('goodput_tokens', 0)} tok "
          f"contended vs {vic_solo.get('goodput_tokens', 0)} solo → "
          f"{frac:.2f}x fair share (victim finished "
          f"{vic_cont.get('finished', 0)}/{args.tenancy_victim_requests}, "
          f"aggressor finished {agg_cont.get('finished', 0)}/"
          f"{args.tenancy_aggressor_requests})")
    return row


def bench_http(args, overload_row):
    """HTTP front-end overhead: the overload shed-on workload replayed
    through the asyncio server (real sockets, SSE streaming) against the
    in-process shed-on run as baseline. Client-side TTFT is measured from
    each request's *scheduled* Poisson arrival (open-loop — queueing the
    client causes counts, like the in-process bench), p99 over FINISHED
    requests only. The gate (--max-http-ttft-overhead) bounds how much
    tail latency the HTTP layer — parsing, the cross-thread mailbox, SSE
    fan-out — may add on top of the engine itself. The run ends with a
    graceful drain and the engine's conservation check."""
    import threading
    from collections import Counter

    from repro.serving.server import (ServerConfig, start_in_thread,
                                      stream_completion)

    cfg = get_smoke_config(args.arch)
    params = model_fns(cfg).init_params(jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, EngineConfig(
        n_slots=args.overload_slots, capacity=args.capacity,
        page_size=args.page_size, plan_packed=False,
        max_waiting=args.overload_max_waiting))
    plens = [4, 8, 12]
    h = start_in_thread(eng, ServerConfig(), warmup_lens=plens)

    n = args.overload_requests
    rng = np.random.default_rng(11)     # same seed as the in-process bench
    arrivals = np.cumsum(rng.exponential(1.0 / args.overload_rate, size=n))
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.choice(plens))).tolist()
               for _ in range(n)]
    results = [None] * n
    t0 = time.perf_counter()

    def client(i):
        sched = t0 + arrivals[i]
        delay = sched - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        r = stream_completion(
            "127.0.0.1", h.port,
            {"prompt": prompts[i], "max_tokens": args.overload_gen,
             "deadline_s": args.overload_deadline})
        results[i] = (r, sched)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    h.request_drain()
    h.wait_closed(120)
    assert h.server.conservation_ok, "HTTP bench leaked slots/pages"

    counts: Counter = Counter()
    ttfts = []
    for r, sched in results:
        status = (r.final or {}).get("status", "FAILED").lower()
        counts[status] += 1
        if status == "finished" and r.t_first > 0:
            ttfts.append(r.t_first - sched)
    p = (lambda q: float(np.percentile(ttfts, q))) if ttfts else lambda q: 0.0
    inproc_p99 = overload_row["shed_on"]["ttft_s"]["p99"]
    http_p99 = p(99)
    row = {
        "section": "http", "arch": args.arch,
        "rate": args.overload_rate, "requests": n,
        "gen": args.overload_gen, "slots": args.overload_slots,
        "max_waiting": args.overload_max_waiting,
        "deadline_s": args.overload_deadline,
        "ttft_s": {"p50": p(50), "p95": p(95), "p99": http_p99},
        "status_counts": dict(counts),
        "inproc_p99_s": inproc_p99,
        "http_vs_inproc_p99": (http_p99 / inproc_p99
                               if inproc_p99 > 0 else 0.0),
        "restarts": h.server.host.restarts,
        "leaked_pages": 0,              # asserted via conservation above
    }
    print(f"http rate={args.overload_rate}/s x{n} req, "
          f"{args.overload_slots} slots: server-side p99 TTFT "
          f"{http_p99*1e3:.1f} ms vs in-process shed-on "
          f"{inproc_p99*1e3:.1f} ms → "
          f"{row['http_vs_inproc_p99']:.2f}x overhead "
          f"(finished {counts.get('finished', 0)}, rejected "
          f"{counts.get('rejected', 0)}, timeout {counts.get('timeout', 0)})")
    return row


def bench_static(cfg, params, prompts, gens, batch, capacity):
    """Legacy one-batch-at-a-time loop at equal useful load: fixed batches
    in arrival order, uniform prompt padding, every batch decoded to its
    LONGEST request before the next batch starts. Only each request's own
    gens[i] tokens count as useful output."""
    chunks = [list(range(i, min(i + batch, len(prompts))))
              for i in range(0, len(prompts), batch)]

    def run():
        toks = 0
        for idx in chunks:
            pmax = max(len(prompts[i]) for i in idx)
            steps = max(gens[i] for i in idx)
            sc = ServeConfig(batch=len(idx), prompt_len=pmax,
                             gen_tokens=steps, capacity=capacity)
            generate(cfg, params, sc, log=lambda *a: None)
            toks += sum(gens[i] for i in idx)
        return toks

    # warmup populates serve._jitted_fns' compiled programs for every chunk
    # shape, so the timed pass reuses them
    run()
    t0 = time.perf_counter()
    toks = run()
    dt = time.perf_counter() - t0
    return {"tok_s": toks / dt, "elapsed_s": dt, "tokens": toks}


_SHARDED_SNIPPET = r"""
import dataclasses, json, time
import numpy as np
from repro.configs import get_smoke_config
from repro.launch.serve import build_params
from repro.serving.engine import EngineConfig, InferenceEngine

A = json.loads(%s)
tp = A["tp"]
cfg = dataclasses.replace(
    get_smoke_config(A["arch"]), num_kv_heads=4,
    attn_impl="dense", dtype="float32", cache_dtype="float32")
params = build_params(cfg, log=lambda *a, **k: None, decode_m=A["slots"])
eng = InferenceEngine(cfg, params, EngineConfig(
    n_slots=A["slots"], capacity=A["capacity"],
    page_size=A["page_size"], kv_pages=A["pages_per_device"] * tp,
    mesh_model=tp, preempt_after_stalls=2))
eng.warmup([8])
rng = np.random.default_rng(3)
for _ in range(A["requests"]):
    eng.submit(rng.integers(0, cfg.vocab_size,
                            (int(rng.integers(6, 12)),)).tolist(),
               max_new_tokens=A["gen"])
peak_pages, steps = 0, 0
t0 = time.perf_counter()
while eng.sched.has_work() and steps < 5000:
    eng.step()
    steps += 1
    used = eng.pool.n_pages - eng.pool.idle_pages() - 1   # minus null page
    peak_pages = max(peak_pages, used)
dt = time.perf_counter() - t0
eng.check_conservation()
toks = sum(len(r.generated) for r in eng.sched.finished)
st = eng.stats_snapshot()
print("RESULT " + json.dumps({
    "tp": tp, "kv_pages": int(eng.pool.n_pages),
    "peak_pages": int(peak_pages),
    "peak_resident_tokens": int(peak_pages * A["page_size"]),
    "tok_s": toks / dt, "tokens": int(toks), "steps": steps,
    "drained": not eng.sched.has_work(),
    "kv_bytes_read": int(st["kv_bytes_read"]),
    "kv_bytes_read_device": int(st["kv_bytes_read_device"])}))
"""


def bench_sharded(args):
    """Tensor-parallel capacity section: one engine per mesh size, each in
    a fresh subprocess with ``--xla_force_host_platform_device_count=N``
    (the bench process itself keeps one device). The KV page budget is
    fixed PER DEVICE, so head-parallel pool sharding lets mesh N provision
    ~N× the logical pages; under the same oversubscribed traffic the gated
    metric is peak resident tokens at mesh 2 vs mesh 1. tok/s is reported
    per mesh for context, not gated — fake CPU devices time-slice one
    host, so sharded wall-clock says nothing about real multi-chip.

    Refused on a TPU host: the children would run on fake CPU devices
    while this process holds the chip, and report CPU numbers from the
    chip machine. The chip's mesh check is ``chip_smoke.py --chips 4``."""
    if jax.default_backend() == "tpu":
        raise SystemExit(
            "the sharded section runs mesh engines on fake CPU devices; on "
            "a TPU host run `python chip_smoke.py --chips 4` instead")
    per = {}
    for tp in args.sharded_meshes:
        spec = json.dumps({
            "arch": args.arch, "tp": tp, "slots": args.sharded_slots,
            "capacity": args.sharded_capacity,
            "page_size": args.sharded_page_size,
            "pages_per_device": args.sharded_pages_per_device,
            "requests": args.sharded_requests, "gen": args.sharded_gen})
        env = dict(os.environ,
                   XLA_FLAGS=("--xla_force_host_platform_device_count="
                              f"{tp}"),
                   JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.environ.get("PYTHONPATH", "src"))
        proc = subprocess.run(
            [sys.executable, "-c", _SHARDED_SNIPPET % repr(spec)],
            capture_output=True, text=True, timeout=900, env=env)
        if proc.returncode != 0:
            raise SystemExit(
                f"sharded bench subprocess (mesh {tp}) failed:\n"
                f"{proc.stderr[-3000:]}")
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("RESULT ")][-1]
        per[tp] = r = json.loads(line[len("RESULT "):])
        print(f"sharded mesh={tp}: peak {r['peak_resident_tokens']} "
              f"resident tokens ({r['peak_pages']}/{r['kv_pages'] - 1} "
              f"data pages), {r['tok_s']:.1f} tok/s, "
              f"kv/device {r['kv_bytes_read_device']}")
    ratio = (per[2]["peak_resident_tokens"]
             / per[1]["peak_resident_tokens"]
             if 1 in per and 2 in per else 0.0)
    if ratio:
        print(f"sharded capacity mesh-2 vs mesh-1: {ratio:.2f}x at a "
              f"fixed per-device page budget")
    return {"section": "sharded", "arch": args.arch,
            "meshes": list(args.sharded_meshes),
            "pages_per_device": args.sharded_pages_per_device,
            "page_size": args.sharded_page_size,
            "per_mesh": {str(tp): per[tp] for tp in per},
            "capacity_ratio_2v1": ratio}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--slots", type=int, nargs="+", default=[4, 8])
    ap.add_argument("--keeps", type=float, nargs="+", default=[0.0, 0.25])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--prompt-lens", type=int, nargs="+", default=[8, 16, 24])
    # serving-scale overrides: the smoke configs are sized for test speed
    # (d_model=64), where per-dispatch overhead swamps weight traffic and
    # NO weight format can matter. The bench defaults scale the model up
    # until the decode step is weight-bound — the regime GRIM targets.
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--d-ff", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=0, help="0 → smoke value")
    ap.add_argument("--bcr-block", type=int, default=128)
    ap.add_argument("--min-packed-vs-dense", type=float, default=0.0,
                    help="exit 1 if packed engine tok/s ÷ dense engine "
                         "tok/s at the largest --slots falls below this")
    # long-context paged-KV section: capacity ≥ 2048 with mixed mostly-
    # short prompts — the regime where masked-dense decode pays capacity
    # bandwidth every step and block paging pays live tokens
    ap.add_argument("--long-context", action="store_true",
                    help="also run the paged-vs-masked long-context bench")
    ap.add_argument("--long-capacity", type=int, default=4096)
    ap.add_argument("--long-prompt-lens", type=int, nargs="+",
                    default=[16, 64, 256])
    ap.add_argument("--long-requests", type=int, default=10)
    ap.add_argument("--long-gen", type=int, default=12)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--min-paged-vs-masked", type=float, default=0.0,
                    help="exit 1 if long-context paged tok/s ÷ masked-"
                         "dense tok/s falls below this")
    # shared-prefix prefix-cache section: every request shares one system
    # prompt; wave 2 admissions hit the cache and prefill only their
    # short user suffixes
    ap.add_argument("--shared-prefix", action="store_true",
                    help="also run the prefix-cache TTFT/pages bench")
    ap.add_argument("--system-len", type=int, default=96)
    ap.add_argument("--sfx-lens", type=int, nargs="+", default=[4, 8, 12])
    ap.add_argument("--min-prefix-ttft-speedup", type=float, default=0.0,
                    help="exit 1 if prefix-hit admission TTFT speedup "
                         "over cold prefill falls below this")
    # speculative-decode section: plain paged decode vs draft→verify→
    # accept under the high-acceptance oracle drafter (and a small real
    # drafter for the overhead floor)
    ap.add_argument("--speculative", action="store_true",
                    help="also run the speculative-decode bench")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="draft tokens per verify dispatch")
    ap.add_argument("--spec-iters", type=int, default=3,
                    help="timed passes per speculative config (best "
                         "kept): the drained runs are seconds long, so "
                         "best-of-N de-noises the gated ratio")
    ap.add_argument("--min-spec-vs-plain", type=float, default=0.0,
                    help="exit 1 if oracle-drafter speculative tok/s ÷ "
                         "plain paged decode tok/s at the largest --slots "
                         "falls below this")
    # quantized-serving section: fp paged engine vs int8 KV pages (and,
    # when --keeps has a packed entry, fp vs int8 packed BCR weights)
    ap.add_argument("--quantized", action="store_true",
                    help="also run the int8-KV / int8-weight bench")
    ap.add_argument("--max-quant-kv-ratio", type=float, default=0.0,
                    help="exit 1 if int8 KV bytes/step ÷ fp paged bytes/"
                         "step exceeds this (0 → no gate)")
    ap.add_argument("--max-quant-divergence", type=float, default=-1.0,
                    help="exit 1 if int8 KV flips this much more of the "
                         "teacher-forced greedy decisions (vs the fp32 "
                         "cache oracle) than the bf16 baseline cache "
                         "does (< 0 → no gate)")
    ap.add_argument("--min-quant-vs-fp", type=float, default=0.0,
                    help="exit 1 if int8-KV tok/s ÷ fp paged tok/s falls "
                         "below this (0 → no gate)")
    ap.add_argument("--overload", action="store_true",
                    help="overload section: arrivals >> service rate, load "
                         "shedding on vs off (bounded queue + deadlines)")
    ap.add_argument("--overload-rate", type=float, default=400.0,
                    help="overload arrival rate (req/s, Poisson)")
    ap.add_argument("--overload-requests", type=int, default=64)
    ap.add_argument("--overload-gen", type=int, default=16)
    ap.add_argument("--overload-slots", type=int, default=2)
    ap.add_argument("--overload-max-waiting", type=int, default=4,
                    help="waiting-queue bound for the shed-on run")
    ap.add_argument("--overload-deadline", type=float, default=0.25,
                    help="per-request deadline (s) for the shed-on run")
    ap.add_argument("--max-overload-p99-ratio", type=float, default=0.0,
                    help="gate: shed-on p99 TTFT (FINISHED requests) must "
                         "be at most this fraction of the shed-off p99 "
                         "(0 → no gate)")
    ap.add_argument("--slo", action="store_true",
                    help="SLO-admission section: the overload workload "
                         "with the predictive seat-time estimator on, vs "
                         "a reactive run that admits everything and "
                         "enforces the same deadlines after the fact")
    ap.add_argument("--slo-deadline", type=float, default=0.5,
                    help="per-request deadline (s) for the --slo runs: "
                         "long enough that the structural gap between "
                         "stop-admitting-at-slack-x-deadline and "
                         "fill-right-up-to-the-deadline dominates "
                         "estimator noise in the gated p99 ratio")
    ap.add_argument("--slo-slack", type=float, default=0.8,
                    help="admission slack for the --slo run: admit "
                         "while estimated finish ≤ slack × deadline "
                         "(< 1 leaves margin so borderline admits don't "
                         "miss their deadline on a noisy box)")
    ap.add_argument("--max-slo-p99-ratio", type=float, default=0.0,
                    help="gate: predictive-admission p99 TTFT (FINISHED "
                         "requests) must be at most this fraction of the "
                         "reactive shed-on p99, AND the predictive run "
                         "must have zero waiting-queue timeouts "
                         "(0 → no gate)")
    ap.add_argument("--tenancy", action="store_true",
                    help="tenant-isolation section: aggressor flood vs a "
                         "modest victim under weighted fair queueing")
    ap.add_argument("--tenancy-victim-rate", type=float, default=6.0)
    ap.add_argument("--tenancy-victim-requests", type=int, default=12)
    ap.add_argument("--tenancy-aggressor-rate", type=float, default=200.0)
    ap.add_argument("--tenancy-aggressor-requests", type=int, default=96)
    ap.add_argument("--tenancy-gen", type=int, default=16)
    ap.add_argument("--tenancy-deadline", type=float, default=0.75,
                    help="per-request deadline (s) for both tenants — "
                         "turns lost share into measurable loss")
    ap.add_argument("--min-victim-goodput-frac", type=float, default=0.0,
                    help="gate: contended victim goodput tokens must be "
                         "at least this fraction of the victim-solo run "
                         "(0 → no gate)")
    # tensor-parallel sharded section: one engine per mesh size in fresh
    # subprocesses over fake CPU devices; gated on CAPACITY, not speed —
    # head-parallel KV sharding means a fixed per-device page budget
    # provisions mesh× the logical pages
    ap.add_argument("--sharded", action="store_true",
                    help="also run the tensor-parallel capacity bench "
                         "(subprocess per mesh size with fake CPU devices)")
    ap.add_argument("--sharded-meshes", type=int, nargs="+",
                    default=[1, 2, 4])
    ap.add_argument("--sharded-pages-per-device", type=int, default=33,
                    help="KV pages provisioned PER DEVICE (the engine "
                         "gets pages_per_device × mesh logical pages)")
    ap.add_argument("--sharded-page-size", type=int, default=4)
    ap.add_argument("--sharded-slots", type=int, default=8)
    ap.add_argument("--sharded-capacity", type=int, default=32)
    ap.add_argument("--sharded-requests", type=int, default=12)
    ap.add_argument("--sharded-gen", type=int, default=16)
    ap.add_argument("--min-sharded-capacity-ratio", type=float,
                    default=0.0,
                    help="exit 1 if mesh-2 peak resident tokens ÷ mesh-1 "
                         "at a fixed per-device page budget falls below "
                         "this (0 → no gate)")
    ap.add_argument("--http", action="store_true",
                    help="HTTP front-end section: the overload shed-on "
                         "workload replayed through the asyncio server "
                         "(needs --overload for the in-process baseline)")
    ap.add_argument("--max-http-ttft-overhead", type=float, default=0.0,
                    help="gate: server-side p99 TTFT over HTTP must be at "
                         "most this multiple of the in-process shed-on "
                         "p99 (0 → no gate)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    results = []
    for keep in args.keeps:
        cfg = scaled_cfg(args, keep)
        fns = model_fns(cfg)
        params = fns.init_params(jax.random.PRNGKey(0))
        if keep > 0:
            params = pack_params(cfg, params)
        prompts, gens = make_requests(cfg, args.requests, args.prompt_lens,
                                      args.gen)
        for n_slots in args.slots:
            eng = bench_engine(cfg, params, prompts, gens, n_slots,
                               args.capacity)
            sta = bench_static(cfg, params, prompts, gens, n_slots,
                               args.capacity)
            row = {"arch": args.arch, "keep_frac": keep, "batch": n_slots,
                   "d_model": cfg.d_model, "engine": eng, "static": sta,
                   "speedup": eng["tok_s"] / sta["tok_s"]}
            results.append(row)
            print(f"keep={keep} batch={n_slots}: engine "
                  f"{eng['tok_s']:.1f} tok/s (occ "
                  f"{eng['mean_occupancy']:.2f}) vs static "
                  f"{sta['tok_s']:.1f} tok/s → {row['speedup']:.2f}x")

    # packed-vs-dense engine throughput at equal load: the GRIM claim is
    # that the pruning rate shows up as decode speedup, not storage alone
    dense = {r["batch"]: r["engine"]["tok_s"]
             for r in results if r["keep_frac"] == 0}
    ratios = {}
    for r in results:
        if r["keep_frac"] > 0 and r["batch"] in dense:
            ratio = r["engine"]["tok_s"] / dense[r["batch"]]
            ratios[f"keep{r['keep_frac']}_batch{r['batch']}"] = ratio
            r["packed_vs_dense"] = ratio
            print(f"packed keep={r['keep_frac']} batch={r['batch']}: "
                  f"{ratio:.2f}x dense engine")

    long_row = None
    if args.long_context:
        long_row = bench_long_context(args)
        results.append(long_row)

    prefix_row = None
    if args.shared_prefix:
        prefix_row = bench_shared_prefix(args)
        results.append(prefix_row)

    spec_row = None
    if args.speculative:
        spec_row = bench_speculative(args)
        results.append(spec_row)

    quant_row = None
    if args.quantized:
        quant_row = bench_quantized(args)
        results.append(quant_row)

    overload_row = None
    if args.overload:
        overload_row = bench_overload(args)
        results.append(overload_row)

    slo_row = None
    if args.slo:
        slo_row = bench_overload_slo(args)
        results.append(slo_row)

    tenancy_row = None
    if args.tenancy:
        tenancy_row = bench_tenancy(args)
        results.append(tenancy_row)

    http_row = None
    if args.http:
        if overload_row is None:
            raise SystemExit("--http needs --overload (the in-process "
                             "shed-on run is its baseline)")
        http_row = bench_http(args, overload_row)
        results.append(http_row)

    sharded_row = None
    if args.sharded:
        sharded_row = bench_sharded(args)
        results.append(sharded_row)

    payload = {"benchmark": "serve", "packed_vs_dense": ratios,
               "results": results}
    if long_row is not None:
        payload["paged_vs_masked"] = long_row["paged_vs_masked"]
        payload["long_context"] = long_row
    if prefix_row is not None:
        payload["prefix_ttft_speedup"] = prefix_row["prefix_ttft_speedup"]
        payload["shared_prefix"] = prefix_row
    if spec_row is not None:
        payload["spec_vs_plain"] = spec_row["spec_vs_plain"]
        payload["speculative"] = spec_row
    if quant_row is not None:
        payload["quant_kv_bytes_ratio"] = quant_row["kv_bytes_ratio"]
        payload["quant_divergence_rate"] = quant_row["excess_flip_rate"]
        payload["quant_vs_fp"] = quant_row["quant_vs_fp"]
        payload["quantized"] = quant_row
    if overload_row is not None:
        payload["overload_p99_ratio"] = overload_row["overload_p99_ratio"]
        payload["overload"] = overload_row
    if slo_row is not None:
        payload["slo_p99_ratio"] = slo_row["slo_p99_ratio"]
        payload["overload_slo"] = slo_row
    if tenancy_row is not None:
        payload["victim_goodput_frac"] = tenancy_row["victim_goodput_frac"]
        payload["tenancy"] = tenancy_row
    if http_row is not None:
        payload["http_ttft_overhead"] = http_row["http_vs_inproc_p99"]
        payload["http"] = http_row
    if sharded_row is not None:
        payload["sharded_capacity_ratio"] = (
            sharded_row["capacity_ratio_2v1"])
        payload["sharded"] = sharded_row
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")

    if (args.max_quant_kv_ratio > 0 or args.max_quant_divergence >= 0
            or args.min_quant_vs_fp > 0):
        if quant_row is None:
            raise SystemExit("quantized gates need --quantized")
        if (args.max_quant_kv_ratio > 0
                and quant_row["kv_bytes_ratio"] > args.max_quant_kv_ratio):
            raise SystemExit(
                f"PERF REGRESSION: int8 KV reads "
                f"{quant_row['kv_bytes_ratio']:.3f}x fp paged bytes/step "
                f"at batch {quant_row['batch']} "
                f"(> {args.max_quant_kv_ratio}x allowed)")
        if (args.max_quant_divergence >= 0
                and quant_row["excess_flip_rate"]
                > args.max_quant_divergence):
            raise SystemExit(
                f"QUALITY REGRESSION: int8 KV flips "
                f"{quant_row['excess_flip_rate']:.4f} more greedy "
                f"decisions than the bf16 baseline cache, teacher-forced "
                f"vs the fp32 oracle (> {args.max_quant_divergence} "
                f"allowed; int8 {quant_row['forced_flip_rate']:.4f}, "
                f"bf16 {quant_row['baseline_flip_rate']:.4f}, "
                f"free-running divergence "
                f"{quant_row['divergence_rate']:.4f})")
        if (args.min_quant_vs_fp > 0
                and quant_row["quant_vs_fp"] < args.min_quant_vs_fp):
            raise SystemExit(
                f"PERF REGRESSION: int8-KV engine "
                f"{quant_row['quant_vs_fp']:.2f}x fp paged tok/s at batch "
                f"{quant_row['batch']} (< {args.min_quant_vs_fp}x "
                f"required)")

    if args.max_overload_p99_ratio > 0:
        if overload_row is None:
            raise SystemExit("--max-overload-p99-ratio needs --overload")
        if (overload_row["overload_p99_ratio"]
                > args.max_overload_p99_ratio):
            raise SystemExit(
                f"TAIL LATENCY REGRESSION: with shedding on, p99 TTFT is "
                f"{overload_row['overload_p99_ratio']:.3f}x the unbounded-"
                f"queue p99 under overload "
                f"(> {args.max_overload_p99_ratio}x allowed — shedding "
                f"must keep the admitted tail bounded)")

    if args.max_slo_p99_ratio > 0:
        if slo_row is None:
            raise SystemExit("--max-slo-p99-ratio needs --slo")
        if slo_row["timeouts_waiting"] > 0:
            raise SystemExit(
                f"ADMISSION REGRESSION: {slo_row['timeouts_waiting']} "
                f"requests were admitted by the SLO estimator and then "
                f"timed out in the waiting queue — predictive admission "
                f"must reject provably-doomed requests at submit, not "
                f"admit them to die")
        if slo_row["slo_p99_ratio"] > args.max_slo_p99_ratio:
            raise SystemExit(
                f"TAIL LATENCY REGRESSION: with SLO admission on, p99 "
                f"TTFT of admitted requests is "
                f"{slo_row['slo_p99_ratio']:.3f}x the reactive shed-on "
                f"p99 under the same overload "
                f"(> {args.max_slo_p99_ratio}x allowed — rejecting the "
                f"doomed at submit must not slow the admitted)")

    if args.min_victim_goodput_frac > 0:
        if tenancy_row is None:
            raise SystemExit("--min-victim-goodput-frac needs --tenancy")
        if (tenancy_row["victim_goodput_frac"]
                < args.min_victim_goodput_frac):
            raise SystemExit(
                f"ISOLATION REGRESSION: the victim tenant kept only "
                f"{tenancy_row['victim_goodput_frac']:.2f}x of its solo "
                f"goodput under the aggressor flood "
                f"(< {args.min_victim_goodput_frac}x required — weighted "
                f"fair queueing must protect a tenant offering less than "
                f"its fair share)")

    if args.max_http_ttft_overhead > 0:
        if http_row is None:
            raise SystemExit("--max-http-ttft-overhead needs --http")
        if http_row["http_vs_inproc_p99"] > args.max_http_ttft_overhead:
            raise SystemExit(
                f"TAIL LATENCY REGRESSION: p99 TTFT through the HTTP "
                f"front-end is {http_row['http_vs_inproc_p99']:.2f}x the "
                f"in-process shed-on p99 under the same overload "
                f"(> {args.max_http_ttft_overhead}x allowed — the server "
                f"layer must not dominate the tail)")

    if args.min_sharded_capacity_ratio > 0:
        if sharded_row is None:
            raise SystemExit("--min-sharded-capacity-ratio needs "
                             "--sharded")
        if (sharded_row["capacity_ratio_2v1"]
                < args.min_sharded_capacity_ratio):
            raise SystemExit(
                f"CAPACITY REGRESSION: mesh-2 peak resident tokens "
                f"{sharded_row['capacity_ratio_2v1']:.2f}x mesh-1 at a "
                f"fixed per-device page budget "
                f"(< {args.min_sharded_capacity_ratio}x required — "
                f"head-parallel KV sharding must scale pool capacity "
                f"with the mesh)")

    if args.min_spec_vs_plain > 0:
        if spec_row is None:
            raise SystemExit("--min-spec-vs-plain needs --speculative")
        if spec_row["spec_vs_plain"] < args.min_spec_vs_plain:
            raise SystemExit(
                f"PERF REGRESSION: speculative decode "
                f"{spec_row['spec_vs_plain']:.2f}x plain paged decode at "
                f"batch {spec_row['batch']} under the high-acceptance "
                f"drafter (< {args.min_spec_vs_plain}x required)")

    if args.min_prefix_ttft_speedup > 0:
        if prefix_row is None:
            raise SystemExit("--min-prefix-ttft-speedup needs "
                             "--shared-prefix")
        if prefix_row["prefix_ttft_speedup"] < args.min_prefix_ttft_speedup:
            raise SystemExit(
                f"PERF REGRESSION: prefix-hit admission TTFT "
                f"{prefix_row['prefix_ttft_speedup']:.2f}x cold prefill "
                f"at batch {prefix_row['batch']} "
                f"(< {args.min_prefix_ttft_speedup}x required)")
        if (prefix_row["pages_allocated"]
                >= prefix_row["pages_allocated_unshared"]):
            raise SystemExit(
                f"PERF REGRESSION: prefix sharing allocated "
                f"{prefix_row['pages_allocated']} pages vs "
                f"{prefix_row['pages_allocated_unshared']} unshared — "
                f"sharing must strictly reduce page demand")

    if args.min_paged_vs_masked > 0:
        if long_row is None:
            raise SystemExit("--min-paged-vs-masked needs --long-context")
        if long_row["paged_vs_masked"] < args.min_paged_vs_masked:
            raise SystemExit(
                f"PERF REGRESSION: paged decode "
                f"{long_row['paged_vs_masked']:.2f}x masked-dense at "
                f"matched occupancy (< {args.min_paged_vs_masked}x "
                f"required)")

    if args.min_packed_vs_dense > 0:
        if not ratios:
            raise SystemExit(
                "--min-packed-vs-dense needs both a dense (0) and a packed "
                "(>0) entry in --keeps to evaluate the gate")
        big = max(r["batch"] for r in results
                  if r.get("keep_frac", 0) > 0)
        worst = min(v for k, v in ratios.items() if k.endswith(f"_batch{big}"))
        if worst < args.min_packed_vs_dense:
            raise SystemExit(
                f"PERF REGRESSION: packed path {worst:.2f}x dense at "
                f"batch {big} (< {args.min_packed_vs_dense}x required)")


if __name__ == "__main__":
    main()
