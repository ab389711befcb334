"""InferenceEngine: continuous-batching serving over (BCR-packed) params.

The engine owns the packed/dense param pytree, a SlotPool (decode cache +
per-slot lengths) and a Scheduler. Each ``step()``:

  1. admits waiting requests into free slots — all admissions of a step
     share ONE batched ``prefill`` (prompts bucketed, rows padded to a
     compiled tier) and seat the resulting KV/state into their slots;
     steady-state backfills are chunked (admission hysteresis, see
     ``EngineConfig.backfill_chunk``) so retirements don't each pay a
     single-row prefill dispatch;
  2. runs ONE jit'd ``decode_step`` over the whole ragged slot batch with a
     per-slot ``cache_len`` vector (donated cache buffers) — with block
     paging on (``EngineConfig.page_size``), the step also gets each
     slot's block-table rows, sliced to the pow2-bucketed live width, so
     KV bytes read scale with live context instead of capacity (pages are
     reserved at admission, allocated on advance, freed on retire —
     admission control requeues requests the page pool cannot cover);
  3. samples per-slot (greedy / temperature / top-k), advances lengths, and
     retires finished requests.

Free slots ride along as masked garbage rows — the per-slot length mask in
``decode_attention`` keeps them from contaminating anything (attention,
MLPs, and recurrent mixers are all row-independent), and admission
overwrites their cache rows. MoE families are served through the
mask-aware router: garbage rows/pad positions are excluded from expert
capacity via the ``token_mask`` the engine threads into prefill and the
``cache_len > 0`` mask decode derives itself, so capacity-factor routing
sees only real tokens.

With ``EngineConfig.prefix_cache`` (paged, pure-attention families) the
page pool doubles as a cross-request prefix cache: admission matches each
prompt against the pool's content-addressed index, adopts the shared
full-page prefix (refcount bump, zero recompute), copy-on-write-
materializes a shared partial final page if the suffix starts mid-page,
and runs ONE bucketed ``prefill_append`` dispatch for just the uncached
suffixes — TTFT and pages allocated scale with what the cache does not
already hold.

With ``EngineConfig.spec_k`` (paged, pure-attention families) every
decode step becomes a speculative draft→verify→accept step: a drafter
(``serving/speculative.py`` — a small second model or a synthetic oracle)
proposes up to ``spec_k`` tokens per slot, ONE ``prefill_append`` verify
dispatch scores pending + drafts against the paged prefix, and
acceptance commits the longest agreeing prefix plus one token from the
target's own distribution. Rejected drafts rewind: the pool truncates
back to the committed frontier and tail pages return to the slot's
reservation (they were allocated this step and never shared/registered).

Tracing: each step records host spans (``serving/tracing.py``) while a
profiler session runs — ``engine.step`` around ``engine.admit``,
``engine.prefill`` per admission group, ``engine.decode.dispatch``,
``engine.decode.wait`` and ``engine.commit`` (or ``engine.spec``) — with
the step's live rows, context tokens, KV-page gauges and the paged
attention kernel's grid and live steps as args; see
``docs/serving.md`` ("Observability").

Thread safety: one reentrant engine lock guards every scheduler/pool
mutation (``submit`` / ``cancel`` / ``step`` / ``reset_stats`` and the
drain/recover hooks), so an asyncio HTTP front-end (``serving/server.py``)
can submit and cancel from its event-loop thread while a dedicated engine
thread runs the step loop. ``stats_snapshot()`` returns a consistent copy
for ``/metrics`` (no torn counters) and ``poll()`` hands cross-thread
callers copies of per-request progress in one lock acquisition.

Prompt padding: for pure-attention families prompts are right-padded to a
power-of-two bucket (causality keeps right-pads invisible to real
positions; ``prefill(..., length=...)`` reads logits at the true last
token). Recurrent families (ssm) prefill at exact prompt length instead —
pads would advance the state. One retrace per distinct length, fine at
serving granularity.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.api import model_fns
from repro.serving.admission import (TenantQuota, TokenBucket,
                                     estimate_seat_steps, request_work_steps)
from repro.serving.faults import StepWatchdog
from repro.serving.kv_slots import PagedSlotPool, SlotPool
from repro.serving.tp import per_device_kv_bytes
from repro.serving.tracing import span
from repro.serving.scheduler import (CANCELLED, FAILED, FINISHED, REJECTED,
                                     TIMEOUT, Request, Scheduler)

PyTree = Any

_PADDED_FAMILIES = ("dense", "vlm")


def sample_tokens(logits: jax.Array, key: jax.Array, temps: jax.Array,
                  topks: jax.Array, use_topk: bool = True) -> jax.Array:
    """Per-slot sampling: temps==0 → greedy; topks>0 → top-k filtering.

    logits (B, V); temps (B,) float; topks (B,) int. Vectorized so one jit
    serves a batch mixing greedy and sampled requests. ``use_topk`` is a
    static flag: the engine passes False when no active request uses top-k,
    skipping the O(V log V) sort on the hot all-greedy decode path.
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)
    z = logits
    if use_topk:
        srt = jnp.sort(logits, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(srt,
                                  jnp.clip(topks - 1, 0, v - 1)[:, None],
                                  axis=1)
        allow = (topks[:, None] <= 0) | (logits >= kth)
        z = jnp.where(allow, logits, -jnp.inf)
    z = z / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(key, z, axis=-1)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


@dataclasses.dataclass
class EngineConfig:
    n_slots: int = 8
    capacity: int = 128
    seed: int = 0
    max_admit_per_step: Optional[int] = None  # None → fill every free slot
    pad_prefill: Optional[bool] = None        # None → auto by model family
    min_bucket: int = 8
    # block-paged KV: page_size > 0 swaps the capacity-dense SlotPool for a
    # PagedSlotPool — attention K/V live in a shared page pool indexed by
    # per-slot block tables, decode reads scale with live lengths instead
    # of n_slots × capacity, and kv_pages (None → full provisioning) lets
    # capacity oversubscribe HBM when requests are short. Ignored for
    # recurrent-state families (no attention K/V to page).
    page_size: int = 0
    kv_pages: Optional[int] = None
    # prefix sharing over the paged pool: admissions adopt cached full-page
    # prompt prefixes (ref-counted, CoW on a shared partial final page) and
    # prefill only the uncached suffix; completed prompts publish their
    # full pages into the pool's LRU-evicted prefix index. Paged,
    # pure-attention families only (recurrent state cannot be adopted).
    prefix_cache: bool = False
    # chunked backfill: in steady state requests retire one at a time, so
    # naive admission runs a single-row prefill per retirement (~20% of
    # step time at batch 8). Hold admissions until `backfill_chunk` can be
    # seated together (or `backfill_max_defer` decode steps pass, or the
    # engine is idle), then run ONE merged prefill dispatch for all of
    # them. 1 disables deferral.
    backfill_chunk: int = 2
    backfill_max_defer: int = 2
    # GA-tune pack-time execution plans for packed weights at engine build
    # (no-op for dense params / already-planned trees); plan_fitness picks
    # the tuner backend — "analytic" roofline (default) or "wallclock"
    # host timing (block_search.wallclock_plan_fitness, opt-in)
    plan_packed: bool = True
    plan_fitness: str = "analytic"
    # speculative decoding: spec_k > 0 makes every decode step a
    # draft→verify→accept step — a drafter proposes up to spec_k tokens
    # per live slot and the target scores all of them plus the pending
    # token in ONE prefill_append dispatch (decode is its S=1 special
    # case), committing 1..spec_k+1 tokens per step. Needs a paged pool
    # (page_size > 0) on a pure-attention family, plus a drafter: either
    # draft_cfg (+ draft_params at engine build — a small causal_lm
    # sharing the target's token space) or an explicit `drafter` object
    # implementing serving/speculative.py's protocol. Requests then need
    # spec_k tokens of slot headroom: prompt + max_new_tokens + spec_k
    # must fit the capacity (the verify dispatch writes draft K/V past
    # the commit frontier before acceptance rolls it back).
    spec_k: int = 0
    draft_cfg: Optional[ModelConfig] = None
    # quantized serving: kv_dtype="int8" stores attention KV pages as
    # symmetric int8 codes + per-row-per-head fp32 scale pools (dequantized
    # inside the paged Pallas kernels — KV bytes/step roughly halve vs
    # bf16); weight_dtype="int8" quantizes every packed BCR weight tile to
    # int8 codes + per-block scales before plan tuning (the roofline then
    # prices halved weight bytes). "" keeps the model's own dtypes.
    kv_dtype: str = ""
    weight_dtype: str = ""
    # lifecycle hardening: max_waiting bounds the waiting queue — beyond it
    # submit() sheds the waiting request with the earliest absolute deadline
    # (ties: oldest rid; no deadline sorts last) as REJECTED.
    # preempt_after_stalls > 0 arms page-pressure preemption: when the FCFS
    # head stalls on pages for more than that many consecutive steps, the
    # youngest RUNNING slot is evicted (its generated tokens fold into its
    # prompt, so re-prefill — cheap under the prefix cache — replays them
    # bit-identically). watchdog_threshold scales the EWMA slow-step
    # detector (0 disables); fault_injector takes a
    # ``serving/faults.py`` FaultInjector for chaos testing.
    max_waiting: Optional[int] = None
    preempt_after_stalls: int = 0
    watchdog_threshold: float = 3.0
    fault_injector: Any = None
    # SLO-aware admission (serving/admission.py): with slo_admission on,
    # submit() event-simulates slot turnover (free slots + per-request
    # remaining work + tier-aware queue depth ahead) against the measured
    # step-time EWMA and rejects a deadline-carrying request at submit
    # when even its *finish* is provably past deadline_s × slo_slack —
    # instead of queueing work that is doomed to TIMEOUT. Prefix-cache
    # hits discount the prefill term (cheap admits are admitted
    # opportunistically). slo_step_time pins the step-time estimate in
    # seconds (0 → use the calibration EWMA, which survives reset_stats
    # but is cleared by warmup so compile steps never pollute it). Every
    # reject/shed computes Request.retry_after_s from the same simulation.
    slo_admission: bool = False
    slo_slack: float = 1.0
    slo_step_time: float = 0.0
    # tensor-parallel serving: mesh_model > 1 runs every engine program as
    # one jit(shard_map) over a ("model",) mesh of that many devices —
    # projections column-parallel (output dim / BCR row blocks sharded,
    # re-replicated by all-gathers so greedy tokens stay bit-identical to
    # single-device), attention head-parallel with the paged KV pool (and
    # any int8 scale pools) split along Hkv. Per-device pool memory drops
    # to 1/mesh, so at a fixed per-device page budget the engine provisions
    # mesh× the logical pages (resident-token capacity scales with the
    # mesh). Needs a paged pool on a pure-attention dense/vlm family with
    # head counts divisible by the mesh; composes with prefix_cache,
    # spec_k and kv/weight int8. See repro.serving.tp.
    mesh_model: int = 1
    # per-tenant isolation: tenant_quotas maps tenant -> TenantQuota
    # (rate/burst token bucket, concurrent-request cap, KV page budget,
    # WFQ weight); default_tenant_quota applies to tenants not listed
    # (None → unlimited). Quota rejects are REJECTED with a computed
    # retry_after_s; WFQ weights feed the scheduler's admission order.
    tenant_quotas: Optional[Dict[str, TenantQuota]] = None
    default_tenant_quota: Optional[TenantQuota] = None


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params: PyTree,
                 ec: Optional[EngineConfig] = None, *,
                 draft_params: PyTree = None, drafter: Any = None,
                 clock: Optional[Callable[[], float]] = None):
        if cfg.family == "encdec":
            raise NotImplementedError(
                "InferenceEngine serves decoder-only families; encdec "
                "prefill needs encoder frames and a different cache tree")
        ec = ec or EngineConfig()
        # one reentrant lock around every scheduler/pool mutation: submit/
        # cancel/step/reset_stats (and the drain/recover hooks) are safe
        # under cross-thread callers — reentrant because step() itself
        # cancels (fault injection) and recovers
        self._elock = threading.RLock()
        if ec.kv_dtype:
            if ec.kv_dtype != "int8":
                raise ValueError(f"unsupported kv_dtype {ec.kv_dtype!r}")
            cfg = dataclasses.replace(cfg, kv_dtype=ec.kv_dtype)
        self.cfg = cfg
        self.ec = ec
        if ec.weight_dtype and params is not None:
            if ec.weight_dtype != "int8":
                raise ValueError(
                    f"unsupported weight_dtype {ec.weight_dtype!r}")
            # quantize BEFORE planning so the tuner's roofline prices the
            # halved weight-byte traffic of int8 tiles
            from repro.kernels.plan import quantize_packed_params
            params = quantize_packed_params(params)
        if ec.plan_packed and params is not None:
            # GRIM's compile step at engine build: attach GA-tuned
            # execution plans to packed weights (default plans tune for
            # this engine's decode batch; plans the packer already tuned —
            # e.g. pack_params(decode_m=...) — are preserved) and fuse
            # shared-activation projection groups
            from repro.kernels.plan import plan_params
            params = plan_params(params, m=ec.n_slots,
                                 fitness=ec.plan_fitness,
                                 fitness_impl=cfg.kernel_impl)
        self.params = params
        self.fns = fns = model_fns(cfg)
        self.paged = bool(ec.page_size) and cfg.family != "ssm"
        if self.paged:
            self.pool: Any = PagedSlotPool(
                fns.init_cache, ec.n_slots, ec.capacity,
                page_size=ec.page_size, n_pages=ec.kv_pages)
        else:
            self.pool = SlotPool(fns.init_cache, ec.n_slots, ec.capacity)
        self.sched = Scheduler(ec.n_slots)
        self.pad_prefill = (cfg.family in _PADDED_FAMILIES
                            if ec.pad_prefill is None else ec.pad_prefill)
        # prefix sharing needs every mixer to read its history from pages:
        # recurrent mixers (ssm/hybrid) carry state that cannot be adopted
        self.prefix_cache = (bool(ec.prefix_cache) and self.paged
                             and cfg.family in _PADDED_FAMILIES
                             and fns.prefill_append is not None)
        # speculative decoding: verification is a prefill_append dispatch
        # and rollback rewinds paged K/V, so it needs the paged pool and a
        # pure-attention stack (recurrent mixers cannot rewind state)
        self.spec = int(ec.spec_k) > 0
        if self.spec:
            from repro.models.causal_lm import layer_plan as _lp
            if not (self.paged and fns.prefill_append is not None
                    and all(m == "attn" for m, _ in _lp(cfg))):
                raise ValueError(
                    "spec_k > 0 needs a block-paged pool (page_size > 0) "
                    "on a pure-attention family: verification runs "
                    "through prefill_append and rollback rewinds pages")
            if drafter is None:
                from repro.serving.speculative import DraftModel
                if ec.draft_cfg is None or draft_params is None:
                    raise ValueError(
                        "spec_k > 0 needs a drafter: pass draft_cfg + "
                        "draft_params, or a drafter object")
                if ec.draft_cfg.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        "drafter must share the target's token space")
                drafter = DraftModel(ec.draft_cfg, draft_params,
                                     ec.n_slots, ec.capacity,
                                     min_bucket=ec.min_bucket)
        self.drafter = drafter
        self._rng = np.random.default_rng(ec.seed)
        # injectable clock: deadlines, latency timestamps and the watchdog
        # all read it, so tests drive time deterministically (FakeClock)
        self._clock: Callable[[], float] = clock or time.perf_counter
        self.faults = ec.fault_injector
        self._step_idx = -1      # engine step counter (fault schedule index)
        self._stall_steps = 0    # consecutive fully-page-stalled steps
        # admission-estimator step-time calibration: a second EWMA beside
        # the watchdog's that SURVIVES reset_stats (the watchdog is
        # recreated fresh per reset, so its EWMA is useless right after
        # warmup). warmup() clears it so compile-heavy steps never seed it.
        self._step_time = 0.0
        self._buckets: Dict[str, TokenBucket] = {}   # tenant rate limiters
        if ec.tenant_quotas:
            self.sched.weights = {t: q.weight
                                  for t, q in ec.tenant_quotas.items()}
        # per-decode-step KV traffic accounting (BENCH/bench reporting):
        # bytes one cache position (K+V + any sibling scale leaves, all
        # attention layers) costs to read — derived from the ACTUAL pool
        # leaves, so int8 pools report their real (halved + scale) traffic
        # instead of an assumed c_dtype width. Under a mesh these are
        # AGGREGATE bytes; the `kv_bytes_read_device` stat divides by the
        # mesh (the pool is fully Hkv-sharded, nothing is replicated).
        self._kv_row_bytes = self._probe_kv_row_bytes()

        # tensor-parallel setup: shard params (column-parallel / BCR row
        # blocks) and the pool (head-parallel) over the mesh, localize the
        # config the model body sees inside shard_map, and remember the
        # spec trees the program wrappers below need. The pool's host-side
        # bookkeeping (block tables, refcounts, prefix index) is untouched
        # — it is replicated host state addressing per-shard page leaves.
        self.tp = max(1, int(ec.mesh_model))
        self._mesh = None
        if self.tp > 1:
            from repro.serving import tp as tp_lib
            reason = tp_lib.shardable(cfg, self.tp, ec.page_size)
            if reason is not None:
                raise ValueError(f"mesh_model={self.tp}: {reason}")
            self._mesh = tp_lib.make_model_mesh(self.tp)
            prepared, self._param_specs = tp_lib.prepare_params(
                self.params, self.tp)
            self.params = tp_lib.placed(prepared, self._param_specs,
                                        self._mesh)
            self._pool_specs = tp_lib.cache_specs(
                cfg, ec.n_slots, ec.capacity, kv_pages=self.pool.n_pages,
                page_size=ec.page_size)
            self.pool.cache = tp_lib.placed(self.pool.cache,
                                            self._pool_specs, self._mesh)
            # prefill returns an UNPAGED per-row cache whose rows admission
            # scatters into the pool; same Hkv axis discovery, no paging
            self._prefill_specs = tp_lib.cache_specs(cfg, 1, 8)
            # the closures below must trace the model with per-shard head
            # counts (the pool spec hands each device its local Hkv slice)
            fns = model_fns(tp_lib.localize_cfg(cfg, self.tp))

        # sampling is fused into the prefill/decode programs: one dispatch
        # per engine step — at small model scale the extra host round-trip
        # of a separate sampling call costs as much as the step itself.
        # Each program also returns a per-row finite-logits flag (a cheap
        # isfinite reduction over the sampled row) riding the transfer the
        # tokens already pay — the host fails ONLY the offending request on
        # a poisoned row instead of propagating garbage tokens.
        def prefill_sample(p, toks, length, mask, key, temps, topks,
                           use_topk):
            logits, pcache = fns.prefill(p, {"tokens": toks,
                                             "length": length,
                                             "token_mask": mask})
            last = logits[:, -1]
            ok = jnp.isfinite(last).all(axis=-1)
            tok = sample_tokens(last, key, temps, topks, use_topk)
            return tok, ok, pcache

        def decode_sample(p, toks, lens, cache, key, temps, topks, bt,
                          use_topk):
            # free slots are garbage rows: lens > 0 ⟺ live request (a
            # live slot always holds at least its prompt), and only live
            # rows may claim MoE expert capacity
            logits, cache = fns.decode_step(
                p, {"tokens": toks, "cache_len": lens,
                    "block_tables": bt,
                    "token_mask": (lens > 0)[:, None]}, cache)
            with jax.named_scope("sample"):
                last = logits[:, -1]
                ok = jnp.isfinite(last).all(axis=-1)
                tok = sample_tokens(last, key, temps, topks, use_topk)
            return tok, ok, cache

        def append_sample(p, toks, plen, slen, cache, bt, key, temps,
                          topks, use_topk):
            logits, cache = fns.prefill_append(
                p, {"tokens": toks, "prefix_len": plen, "length": slen,
                    "block_tables": bt}, cache)
            last = logits[:, -1]
            ok = jnp.isfinite(last).all(axis=-1)
            tok = sample_tokens(last, key, temps, topks, use_topk)
            return tok, ok, cache

        def verify_logits(p, toks, plen, slen, cache, bt, greedy_only):
            # speculative verification: score every suffix position in one
            # dispatch — row j is the target's distribution for the token
            # after suffix position j. Acceptance is host-side, but what
            # crosses the device-host link depends on the batch: all-greedy
            # steps (the static `greedy_only` flag, like decode's
            # `use_topk`) only compare argmaxes, so the (B, S) argmax rows
            # ship instead of (B, S, V) logits; sampled requests need the
            # full p-rows for the acceptance ratio and residual.
            logits, cache = fns.prefill_append(
                p, {"tokens": toks, "prefix_len": plen, "length": slen,
                    "block_tables": bt, "all_logits": True}, cache)
            # finite check over the REAL suffix rows only (pad rows past
            # slen carry garbage by construction)
            pad = jnp.arange(logits.shape[1])[None, :] >= slen[:, None]
            ok = jnp.all(jnp.isfinite(logits).all(axis=2) | pad, axis=1)
            if greedy_only:
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        ok, cache)
            return logits, ok, cache

        if self.tp > 1:
            from jax.sharding import PartitionSpec as P
            from repro.serving.tp import ShardedProgram
            ps, cs, fs = (self._param_specs, self._pool_specs,
                          self._prefill_specs)
            rep = P()
            self._prefill = ShardedProgram(
                prefill_sample, self._mesh,
                in_specs=(ps, rep, rep, rep, rep, rep, rep),
                out_specs=(rep, rep, fs), static_name="use_topk")
            self._decode = ShardedProgram(
                decode_sample, self._mesh,
                in_specs=(ps, rep, rep, cs, rep, rep, rep, rep),
                out_specs=(rep, rep, cs), static_name="use_topk",
                donate_argnums=(3,))
            self._append = (ShardedProgram(
                append_sample, self._mesh,
                in_specs=(ps, rep, rep, rep, cs, rep, rep, rep, rep),
                out_specs=(rep, rep, cs), static_name="use_topk",
                donate_argnums=(4,))
                if fns.prefill_append is not None else None)
            self._verify = (ShardedProgram(
                verify_logits, self._mesh,
                in_specs=(ps, rep, rep, rep, cs, rep),
                out_specs=(rep, rep, cs), static_name="greedy_only",
                donate_argnums=(4,))
                if self.spec else None)
        else:
            self._prefill = jax.jit(prefill_sample,
                                    static_argnames=("use_topk",))
            self._decode = jax.jit(decode_sample,
                                   static_argnames=("use_topk",),
                                   donate_argnums=(3,))
            self._append = (jax.jit(append_sample,
                                    static_argnames=("use_topk",),
                                    donate_argnums=(4,))
                            if fns.prefill_append is not None else None)
            self._verify = (jax.jit(verify_logits,
                                    static_argnames=("greedy_only",),
                                    donate_argnums=(4,))
                            if self.spec else None)

        self._key = jax.random.PRNGKey(ec.seed)
        self._defer_steps = 0   # decode steps the current backfill waited
        # per-slot decode-state rows (host-side mirrors of the ragged batch)
        self._tokens = np.zeros((ec.n_slots, 1), np.int32)
        self._temps = np.zeros((ec.n_slots,), np.float32)
        self._topks = np.zeros((ec.n_slots,), np.int32)
        self.stats: Dict[str, Any] = {}
        self.reset_stats()
        # single choke point for terminal transitions: the scheduler fires
        # this the moment any request enters `finished`, wherever the
        # retire/reject/drop happened — per-tenant counters cannot drift
        self.sched.on_terminal = self._account_terminal

    # -- request intake ----------------------------------------------------

    def submit(self, prompt: Sequence[int], *, max_new_tokens: int = 16,
               temperature: float = 0.0, top_k: int = 0,
               eos_id: Optional[int] = None, arrival_time: float = 0.0,
               deadline_s: float = 0.0, priority: int = 0,
               tenant: str = "") -> int:
        """Enqueue a request; returns its rid. A request the engine can
        NEVER seat (slot capacity / page pool too small) is retired
        immediately as REJECTED — the rid still comes back, so an open-loop
        driver keeps running and reads the status off the finished list.
        ``deadline_s`` > 0 arms a wall-clock deadline (engine clock,
        measured from this submit): expired requests retire as TIMEOUT
        whether waiting or mid-decode. ``priority`` picks the QoS tier:
        higher tiers are admitted first (FCFS within a tier) and lower
        tiers are preferred as shedding/preemption victims. ``tenant``
        names the quota/fairness bucket: over-quota submits are REJECTED
        with a computed ``retry_after_s``, and with ``slo_admission`` on,
        a deadline the occupancy simulation proves unmakeable is rejected
        right here instead of queueing a doomed request.
        Thread-safe: any thread may call this against a stepping engine."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        with self._elock:
            req = Request(
                prompt=prompt, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, eos_id=eos_id,
                arrival_time=arrival_time, deadline_s=float(deadline_s),
                priority=int(priority), tenant=str(tenant),
                submit_time=self._clock())
            self._tenant_stats(req.tenant)["submitted"] += 1
            # speculative decoding scratch: the verify dispatch writes up
            # to spec_k draft K/V rows past the commit frontier before
            # acceptance rolls them back — the slot needs that headroom
            total = prompt.size + max_new_tokens + self._headroom()
            if total > self.ec.capacity:
                self.stats["rejected"] += 1
                return self.sched.reject(
                    req,
                    f"prompt_len {prompt.size} + max_new_tokens "
                    f"{max_new_tokens}"
                    + (f" + spec_k {self.ec.spec_k}" if self.spec else "")
                    + f" exceeds slot capacity {self.ec.capacity}")
            if self.paged:
                need = self.pool.pages_needed(total)
                if need > self.pool.n_pages - 1:
                    self.stats["rejected"] += 1
                    return self.sched.reject(
                        req,
                        f"request needs {need} KV pages but the pool only "
                        f"has {self.pool.n_pages - 1} allocatable pages")
            reason = self._quota_check_locked(req)
            if reason is not None:
                self.stats["rejected"] += 1
                self.stats["quota_rejected"] += 1
                return self.sched.reject(req, reason)
            if self.ec.slo_admission and req.deadline_s > 0:
                reason = self._slo_check_locked(req)
                if reason is not None:
                    self.stats["rejected"] += 1
                    self.stats["slo_rejected"] += 1
                    req.retry_after_s = self._drain_estimate_locked()
                    return self.sched.reject(req, reason)
            rid = self.sched.submit(req)
            if (self.ec.max_waiting
                    and len(self.sched.waiting) > self.ec.max_waiting):
                # load shedding: drop the lowest-tier waiting request least
                # likely to make its deadline — earliest absolute deadline
                # within the tier (no-deadline requests sort last, ties
                # break oldest-rid)
                victim = min(
                    self.sched.waiting,
                    key=lambda r: (r.priority,
                                   (r.submit_time + r.deadline_s)
                                   if r.deadline_s > 0 else float("inf"),
                                   r.rid))
                victim.retry_after_s = self._drain_estimate_locked()
                self.sched.drop_waiting(victim, REJECTED,
                                        "shed: waiting queue full")
                self.stats["shed"] += 1
            return rid

    # -- SLO-aware admission & per-tenant quotas ---------------------------

    def _quota(self, tenant: str) -> Optional[TenantQuota]:
        if self.ec.tenant_quotas and tenant in self.ec.tenant_quotas:
            return self.ec.tenant_quotas[tenant]
        return self.ec.default_tenant_quota

    def _tenant_stats(self, tenant: str) -> Dict[str, Any]:
        key = tenant or "default"
        ts = self.stats["tenants"].get(key)
        if ts is None:
            ts = self.stats["tenants"][key] = dict(
                submitted=0, finished=0, rejected=0, timeout=0,
                cancelled=0, failed=0, tokens=0, goodput_tokens=0)
        return ts

    def _account_terminal(self, req: Request) -> None:
        """scheduler.on_terminal hook: per-tenant counters plus the
        wasted-prefill tally (prompt tokens whose prefill the engine paid
        for a request that never delivered — the cost predictive admission
        exists to avoid)."""
        ts = self._tenant_stats(req.tenant)
        key = req.status.lower()
        ts[key] = ts.get(key, 0) + 1
        ts["tokens"] += len(req.generated)
        if req.status == FINISHED:
            ts["goodput_tokens"] += len(req.generated)
        elif req.admit_time > 0 or req.status == FAILED:
            self.stats["wasted_prefill_tokens"] += req.prompt_len

    def _live_requests(self) -> List[Request]:
        return (list(self.sched.active.values()) + list(self.sched.waiting)
                + list(self.sched.paused.values()))

    def _quota_check_locked(self, req: Request) -> Optional[str]:
        """Returns a rejection reason if the tenant is over quota (and
        sets ``req.retry_after_s`` to the computed backoff), else None."""
        quota = self._quota(req.tenant)
        if quota is None:
            return None
        live = [r for r in self._live_requests() if r.tenant == req.tenant]
        if quota.max_concurrent > 0 and len(live) >= quota.max_concurrent:
            req.retry_after_s = self._drain_estimate_locked()
            return (f"tenant {req.tenant or 'default'!r} at its concurrent-"
                    f"request quota ({quota.max_concurrent})")
        if quota.max_pages > 0 and self.paged:
            held = sum(self.pool.pages_needed(
                r.prompt_len - r.folded + r.max_new_tokens
                + self._headroom()) for r in live)
            need = self.pool.pages_needed(
                req.prompt_len + req.max_new_tokens + self._headroom())
            if held + need > quota.max_pages:
                req.retry_after_s = self._drain_estimate_locked()
                return (f"tenant {req.tenant or 'default'!r} over its KV "
                        f"page budget ({held} held + {need} needed > "
                        f"{quota.max_pages})")
        bucket = self._buckets.get(req.tenant)
        if bucket is None:
            bucket = self._buckets[req.tenant] = TokenBucket(
                quota.rate, quota.burst, clock=self._clock)
        if not bucket.try_take():
            req.retry_after_s = bucket.next_free_s()
            return (f"tenant {req.tenant or 'default'!r} rate-limited "
                    f"({quota.rate:g} req/s, burst {quota.burst})")
        return None

    def _admission_step_time(self) -> float:
        return (self.ec.slo_step_time if self.ec.slo_step_time > 0
                else self._step_time)

    def _seat_steps_locked(self, ahead: List[Request]) -> float:
        """Steps until a slot frees for a request behind ``ahead``, plus
        the backfill-defer allowance (admissions can be held back up to
        ``backfill_max_defer`` steps by chunking hysteresis)."""
        running = [request_work_steps(r.prompt_len, r.folded,
                                      r.max_new_tokens, len(r.generated)) - 1
                   for r in self.sched.active.values()]
        costs = [request_work_steps(w.prompt_len, w.folded,
                                    w.max_new_tokens, len(w.generated))
                 for w in ahead]
        seat = estimate_seat_steps(self.sched.free_slots(), running, costs)
        return seat + self.ec.backfill_max_defer

    def _drain_estimate_locked(self) -> float:
        """Estimated seconds until a NEW request at the back of the whole
        queue could seat — the occupancy-derived Retry-After. 0 when the
        step time is uncalibrated (the HTTP layer floors it)."""
        st = self._admission_step_time()
        if st <= 0:
            return 0.0
        return self._seat_steps_locked(list(self.sched.waiting)) * st

    def _slo_check_locked(self, req: Request) -> Optional[str]:
        """Returns a rejection reason when the occupancy simulation proves
        ``req`` cannot finish inside deadline_s × slo_slack, else None.
        Uncalibrated step time (no measured steps yet) admits everything —
        predictive admission degrades to the reactive PR-7 behavior.
        Prefix-cache hits discount the prefill term toward zero, so cheap
        prefix-hit admits squeak in where a cold prompt would not."""
        st = self._admission_step_time()
        if st <= 0:
            return None
        ahead = [w for w in self.sched.waiting
                 if w.priority >= req.priority]
        seat = self._seat_steps_locked(ahead)
        prefill = 1.0
        if self.prefix_cache:
            hit, _ = self.pool.match_prefix(req.prompt)
            if hit:
                prefill = max(0.25, (req.prompt_len - hit)
                              / max(1, req.prompt_len))
        est_ttft = (seat + prefill) * st
        est_finish = (seat + prefill + req.max_new_tokens) * st
        if est_finish > req.deadline_s * max(self.ec.slo_slack, 1e-6):
            return (f"slo: estimated finish {est_finish:.3f}s (ttft "
                    f"{est_ttft:.3f}s) exceeds deadline "
                    f"{req.deadline_s:g}s at current occupancy")
        return None

    def retry_after_estimate(self) -> float:
        """Occupancy-derived drain estimate in seconds for an arriving
        request (0 when uncalibrated). Thread-safe: the HTTP layer calls
        this for 503s that never reach submit()."""
        with self._elock:
            return self._drain_estimate_locked()

    def pause(self, rid: int) -> bool:
        """Park a live request (slow-client backpressure): a running
        request folds its generated tokens into its prompt and releases
        its slot + KV pages; a waiting one just leaves the queue. The
        request keeps its rid and deadline, can still be cancelled or
        time out, and :meth:`resume` re-enqueues it (re-prefill replays
        the folded tokens bit-identically under greedy). Returns True if
        the rid was live. Thread-safe and idempotent."""
        with self._elock:
            for slot, req in list(self.sched.active.items()):
                if req.rid == rid:
                    self._fold(req)
                    self._release(slot)
                    self.sched.pause(slot)
                    self.stats["paused"] += 1
                    return True
            for req in list(self.sched.waiting):
                if req.rid == rid:
                    self.sched.pause_waiting(req)
                    self.stats["paused"] += 1
                    return True
            return False

    def resume(self, rid: int) -> bool:
        """Re-enqueue a paused request (client caught up). Thread-safe."""
        with self._elock:
            if self.sched.resume(rid) is None:
                return False
            self.stats["resumed"] += 1
            return True

    def reap(self) -> int:
        """Expire deadlines without running a step. The serving host calls
        this on idle ticks so parked (PAUSED) requests — which produce no
        steps — still honor their deadlines. Returns how many expired."""
        with self._elock:
            return len(self._expire_deadlines())

    def cancel(self, rid: int) -> Optional[Request]:
        """Cancel a request by rid, waiting or mid-decode. A running
        request's slot retires immediately and its KV pages / prefix
        refcounts (and any drafter rows) release. Returns the request (now
        CANCELLED), or None if the rid is not live — already terminal or
        unknown — which makes racing a cancel against completion a no-op.
        Thread-safe and idempotent under cross-thread racing: whichever of
        a cancel and a step-side retirement wins the engine lock retires
        the request; the loser sees a non-live rid and no-ops."""
        with self._elock:
            for slot, req in list(self.sched.active.items()):
                if req.rid == rid:
                    self._release(slot)
                    self.stats["cancelled"] += 1
                    return self.sched.retire(slot, CANCELLED)
            for req in list(self.sched.waiting):
                if req.rid == rid:
                    self.stats["cancelled"] += 1
                    return self.sched.drop_waiting(req, CANCELLED)
            req = self.sched.drop_paused(rid, CANCELLED)
            if req is not None:
                self.stats["cancelled"] += 1
            return req

    # -- cross-thread serving hooks (used by serving/server.py) ------------

    def stats_snapshot(self) -> Dict[str, Any]:
        """Consistent copy of ``stats`` for a concurrent reader (``/metrics``):
        taken under the engine lock so no counter is torn mid-step. List-
        valued entries are summarized (mean occupancy) or copied, and live
        queue depths ride along."""
        with self._elock:
            snap: Dict[str, Any] = {}
            for k, v in self.stats.items():
                if k == "slot_occupancy":
                    snap["slot_occupancy_mean"] = (
                        float(np.mean(v)) if v else 0.0)
                elif isinstance(v, list):
                    snap[k] = list(v)
                elif isinstance(v, dict):
                    # tenants: dict of per-tenant counter dicts — deep
                    # enough a copy that the reader can't see torn updates
                    snap[k] = {kk: dict(vv) if isinstance(vv, dict) else vv
                               for kk, vv in v.items()}
                else:
                    snap[k] = v
            snap["active"] = len(self.sched.active)
            snap["waiting"] = len(self.sched.waiting)
            snap["paused_now"] = len(self.sched.paused)
            snap["retry_after_est_s"] = self._drain_estimate_locked()
            return snap

    def poll(self, cursor: int = 0, trim: bool = False
             ) -> Tuple[int, List[Tuple[int, List[int]]],
                        List[Tuple[int, List[int], str, str, float]]]:
        """One-lock progress snapshot for a cross-thread consumer: returns
        ``(new_cursor, live, fin)`` where ``live`` is ``(rid, generated)``
        for every waiting/running/paused request and ``fin`` is
        ``(rid, generated, status, error, retry_after_s)`` for each newly
        terminal request past ``cursor`` on the finished list. All token
        lists are copies. ``trim=True`` drops the consumed finished
        entries instead of advancing the cursor (single-consumer memory
        hygiene for a long-running server; the returned cursor is then
        always 0)."""
        with self._elock:
            fin = [(r.rid, list(r.generated), r.status, r.error,
                    r.retry_after_s)
                   for r in self.sched.finished[cursor:]]
            live = ([(r.rid, list(r.generated))
                     for r in self.sched.active.values()]
                    + [(r.rid, list(r.generated))
                       for r in self.sched.waiting]
                    + [(r.rid, list(r.generated))
                       for r in self.sched.paused.values()])
            if trim:
                del self.sched.finished[cursor:]
                return 0, live, fin
            return len(self.sched.finished), live, fin

    def shed_waiting(self, reason: str) -> List[Request]:
        """Drop every waiting AND paused request as REJECTED (graceful
        drain: running requests finish, queued/parked ones are turned
        away). Returns them."""
        with self._elock:
            dropped: List[Request] = []
            for req in list(self.sched.waiting):
                dropped.append(self.sched.drop_waiting(req, REJECTED, reason))
                self.stats["shed"] += 1
            for rid in list(self.sched.paused):
                dropped.append(self.sched.drop_paused(rid, REJECTED, reason))
                self.stats["shed"] += 1
            return dropped

    def recover(self) -> int:
        """Crash recovery for a supervised step loop: called after
        ``step()`` raised (or a watchdog flagged the loop wedged) to bring
        the scheduler/pool back to a consistent state WITHOUT rebuilding
        the engine — compiled programs, params and the page pool survive.
        Every running request is folded (generated tokens into its prompt,
        so the re-prefill replays them bit-identically under greedy),
        released, and requeued at the front in reverse admission order
        (earliest admit ends leftmost — FCFS is preserved). The prefix
        index is reset (its entries may reference released pages) and the
        stall/defer counters cleared. Returns the survivor count."""
        with self._elock:
            survivors = sorted(self.sched.active.items(),
                               key=lambda kv: (kv[1].admit_time,
                                               kv[1].rid),
                               reverse=True)
            for slot, req in survivors:
                self._fold(req)
                self._release(slot)
                self.sched.requeue(slot)
            if self.prefix_cache:
                self.pool.reset_prefix()
            self._stall_steps = 0
            self._defer_steps = 0
            self.stats["recoveries"] += 1
            return len(survivors)

    # -- internals ---------------------------------------------------------

    def _headroom(self) -> int:
        return self.ec.spec_k if self.spec else 0

    def _probe_kv_row_bytes(self) -> int:
        """Bytes one KV cache position costs to read across all attention
        layers, summed over the pool's actual leaves (dtype-accurate:
        int8 pools count 1 byte/element plus their fp32 scale siblings).
        Paged pools: every page leaf holds ``n_pages × page_size``
        positions. Unpaged: position-bearing leaves are found by probing
        ``init_cache`` at two capacities (recurrent-state leaves have no
        capacity axis and drop out of the difference)."""
        leaves = jax.tree_util.tree_leaves
        if self.paged:
            n_rows = self.pool.n_pages * self.pool.page_size
            return sum(leaf.size // n_rows * leaf.dtype.itemsize
                       for leaf, pax in zip(leaves(self.pool.cache),
                                            leaves(self.pool._page_axes))
                       if pax >= 0)
        c1 = jax.eval_shape(lambda: self.fns.init_cache(1, 8))
        c2 = jax.eval_shape(lambda: self.fns.init_cache(1, 16))
        return sum((b.size - a.size) // 8 * a.dtype.itemsize
                   for a, b in zip(leaves(c1), leaves(c2))
                   if a.shape != b.shape)

    def _bucket(self, n: int) -> int:
        if not self.pad_prefill:
            return n
        b = self.ec.min_bucket
        while b < n:
            b *= 2
        return min(b, self.ec.capacity)

    def _next_key(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k

    def _pow2_widths(self) -> List[int]:
        """Every block-table width the pow2 bucketing can hand a paged
        dispatch (decode, verify and prefill-append all bucket the same
        way) — warmup compiles each of them."""
        widths, w = [], 1
        while True:
            widths.append(min(w, self.pool.max_pages))
            if w >= self.pool.max_pages:
                break
            w *= 2
        return widths

    def _row_tiers(self) -> List[int]:
        """Admission-batch row counts the prefill program is compiled for:
        powers of two up to ``n_slots`` (plus ``n_slots`` itself). Bounds
        retraces to O(log n_slots) per bucket while letting steady-state
        backfills of 2–4 requests share one dispatch."""
        tiers, t = [], 1
        while t < self.ec.n_slots:
            tiers.append(t)
            t *= 2
        tiers.append(self.ec.n_slots)
        return tiers

    def _finish_admission(self, group: List, tok_dev, ok_dev
                          ) -> List[Request]:
        """Shared post-dispatch bookkeeping: record the prefill-sampled
        first token and per-request timing, publish full prompt pages into
        the prefix index when sharing is on. Rows whose logits came back
        non-finite retire as FAILED right here — no token is recorded and
        their (possibly poisoned) prompt never enters the prefix index.
        Returns the failed requests."""
        toks_host = np.asarray(tok_dev)
        ok = np.asarray(ok_dev)
        now = self._clock()
        failed: List[Request] = []
        alive: List = []
        for i, (req, slot) in enumerate(group):
            self._temps[slot] = req.temperature
            self._topks[slot] = req.top_k
            if not ok[i]:
                req.error = "non-finite logits at prefill"
                self._release(slot)
                failed.append(self.sched.retire(slot, FAILED))
                self.stats["failed"] += 1
                continue
            tok = int(toks_host[i])
            req.admit_time = now
            if req.first_token_time == 0.0:
                # preserved across preemption re-admissions: TTFT measures
                # the FIRST first-token, not the re-prefill's
                req.first_token_time = now
            req.generated.append(tok)
            req.token_times.append(now)
            self._tokens[slot, 0] = tok
            if self.prefix_cache:
                self.pool.register_prefix(slot, req.prompt)
            alive.append((req, slot))
        if self.spec and alive:
            # the drafter builds its own full-prompt cache (no prefix
            # sharing on its side — prefix-hit admissions prefill the
            # whole prompt here, at drafter scale)
            self.drafter.admit(alive)
        return failed

    def _admit_group(self, group: List) -> List[Request]:
        """ONE prefill dispatch for a batch of admissions. Prompts are
        right-padded to the largest member's bucket (causality keeps pads
        invisible; per-row ``length`` reads the true last-token logits) and
        rows are padded up to the next compiled row tier; pad rows alias
        slot 0 of the group and are overwritten by the real row
        (reverse-order writes in insert_rows). The token mask keeps pad
        positions/rows out of MoE expert capacity."""
        k = len(group)
        bucket = max(self._bucket(req.prompt_len) for req, _ in group)
        with span("engine.prefill", rows=k, width=bucket,
                  rids=";".join(str(req.rid) for req, _ in group)):
            k_pad = next(t for t in self._row_tiers() if t >= k)
            toks = np.zeros((k_pad, bucket), np.int32)
            lens = np.ones((k_pad,), np.int32)
            mask = np.zeros((k_pad, bucket), bool)
            temps = np.zeros((k_pad,), np.float32)
            topks = np.zeros((k_pad,), np.int32)
            slots = np.zeros((k_pad,), np.int32)
            for i, (req, slot) in enumerate(group):
                p = req.prompt_len
                toks[i, :p] = req.prompt
                lens[i] = p
                mask[i, :p] = True
                temps[i] = req.temperature
                topks[i] = req.top_k
                slots[i] = slot
            slots[k:] = slots[0]
            tok_dev, ok_dev, pcache = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lens),
                jnp.asarray(mask), self._next_key(), jnp.asarray(temps),
                jnp.asarray(topks), use_topk=bool(topks.any()))
            self.pool.insert_rows(pcache, slots, lens[:k])
            self.stats["prefills"] += 1
            self.stats["prefill_rows"] += k
            return self._finish_admission(group, tok_dev, ok_dev)

    def _admit_group_append(self, group: List) -> List[Request]:
        """ONE prefill-append dispatch for a batch of prefix-hit
        admissions: only each request's uncached suffix is computed,
        attending to its adopted prefix pages through the block tables.
        Suffixes are right-padded to a power-of-two bucket and rows to the
        compiled tier; pad rows carry all-zero tables, so their K/V writes
        land in the null page. Before the dispatch, any shared partial
        final page is copy-on-write-materialized (one batched device copy)
        and the suffix pages are allocated so the tables are final."""
        k = len(group)
        bucket = max(self._bucket(req.prompt_len - req.prefix_hit)
                     for req, _ in group)
        with span("engine.prefill", rows=k, width=bucket,
                  rids=";".join(str(req.rid) for req, _ in group)):
            k_pad = next(t for t in self._row_tiers() if t >= k)
            toks = np.zeros((k_pad, bucket), np.int32)
            plens = np.zeros((k_pad,), np.int32)
            slens = np.ones((k_pad,), np.int32)
            temps = np.zeros((k_pad,), np.float32)
            topks = np.zeros((k_pad,), np.int32)
            slots = np.zeros((k_pad,), np.int32)
            cow: List = []
            for i, (req, slot) in enumerate(group):
                hit, p = req.prefix_hit, req.prompt_len
                toks[i, :p - hit] = req.prompt[hit:]
                plens[i] = hit
                slens[i] = p - hit
                temps[i] = req.temperature
                topks[i] = req.top_k
                slots[i] = slot
                if hit % self.pool.page_size:
                    # the suffix starts inside a shared (adopted partial
                    # final) page — materialize a private copy first
                    pair = self.pool.ensure_writable(slot, hit)
                    if pair is not None:
                        cow.append(pair)
                self.pool.ensure(slot, p)     # suffix pages before the scatter
            if cow:
                src, dst = zip(*cow)
                self.pool.copy_pages(np.asarray(src), np.asarray(dst))
            # pow2-bucketed table width, like decode's live-width bucketing:
            # the kernel grid is (B, Hkv, n_cols), so a full-width table made
            # every admission sweep max_pages grid steps per slot even when
            # the longest prompt covered a handful of pages. The bucket covers
            # the widest member's prompt pages; warmup compiles the append
            # program per (suffix bucket × row tier × width).
            need = max(self.pool.pages_needed(req.prompt_len)
                       for req, _ in group)
            w = 1
            while w < need:
                w *= 2
            w = min(w, self.pool.max_pages)
            bt = np.zeros((k_pad, w), np.int32)
            bt[:k] = self.pool.table[slots[:k], :w]
            tok_dev, ok_dev, self.pool.cache = self._append(
                self.params, jnp.asarray(toks), jnp.asarray(plens),
                jnp.asarray(slens), self.pool.cache, jnp.asarray(bt),
                self._next_key(), jnp.asarray(temps), jnp.asarray(topks),
                use_topk=bool(topks.any()))
            for i, (req, slot) in enumerate(group):
                self.pool.lens[slot] = req.prompt_len
            self.stats["prefills"] += 1
            self.stats["prefill_rows"] += k
            self.stats["prefix_hit_tokens"] += int(sum(r.prefix_hit
                                                       for r, _ in group))
            return self._finish_admission(group, tok_dev, ok_dev)

    def _should_admit(self) -> bool:
        """Chunked-backfill hysteresis: batch steady-state admissions into
        one merged prefill instead of a single-row dispatch per retirement.
        Admit immediately when idle or when a full chunk can be seated;
        otherwise defer up to ``backfill_max_defer`` decode steps."""
        ready = min(self.sched.free_slots(), len(self.sched.waiting))
        if ready == 0:
            return False
        chunk = max(1, min(self.ec.backfill_chunk, self.ec.n_slots))
        if chunk <= 1 or not self.sched.active or ready >= chunk:
            return True
        if self._defer_steps >= self.ec.backfill_max_defer:
            return True
        self._defer_steps += 1
        return False

    def step(self) -> List[Request]:
        """One engine iteration; returns every request that reached a
        terminal status this step (FINISHED, but also TIMEOUT, CANCELLED
        and FAILED — check ``Request.status``). Holds the engine lock for
        the whole iteration: cross-thread submit/cancel callers serialize
        against it (they block at most one step)."""
        with self._elock:
            self._step_idx += 1
            with span("engine.step", step=self._step_idx) as sp:
                return self._step(sp)

    def _step(self, sp) -> List[Request]:
        t_step = self._clock()
        with span("engine.admit") as sa:
            finished, admitted, stalled = self._admit()
            sa.set_metadata(admitted=len(admitted), stalled=int(stalled))
        if sp.is_enabled():
            sp.set_metadata(live=len(self.sched.active),
                            waiting=len(self.sched.waiting),
                            **(self.pool.page_gauges() if self.paged
                               else {}))
        if admitted:
            self._defer_steps = 0
            hits = [(r, s) for r, s in admitted if r.prefix_hit > 0]
            cold = [(r, s) for r, s in admitted if r.prefix_hit == 0]
            if hits:
                # prefix-hit admissions share ONE suffix-only dispatch
                finished.extend(self._admit_group_append(hits))
            if cold and self.pad_prefill:
                # padded families: ONE merged dispatch for the whole batch
                # of admissions, whatever their prompt lengths
                finished.extend(self._admit_group(cold))
            elif cold:
                # recurrent families prefill at exact length (pads would
                # advance the state) — group by exact prompt length
                groups: Dict[int, List] = {}
                for req, slot in cold:
                    groups.setdefault(req.prompt_len, []).append((req, slot))
                for group in groups.values():
                    finished.extend(self._admit_group(group))

        # requests whose first (prefill-sampled) token already completed them
        for slot, req in list(self.sched.active.items()):
            if req.is_finished():
                self._release(slot)
                finished.append(self.sched.retire(slot))
        if not self.sched.active:
            self._finish_step(t_step)
            return finished

        self.stats["slot_occupancy"].append(len(self.sched.active))
        if self.spec:
            with span("engine.spec"):
                finished.extend(self._spec_step())
            self._finish_step(t_step)
            return finished
        # each live slot writes one K/V row and attends over len + 1
        write_lens = {slot: int(self.pool.lens[slot]) + 1
                      for slot in self.sched.active}
        with span("engine.decode.dispatch", step=self._step_idx,
                  rows=len(write_lens),
                  ctx_tokens=sum(write_lens.values())) as sd:
            if self.paged:
                bt = self._prepare_paged_writes(write_lens, extra=1)
                if sd.is_enabled():
                    sd.set_metadata(**self._attn_steps(bt.shape[1]))
            else:
                bt = None
                rows = self.ec.n_slots * self.ec.capacity
                self.stats["kv_bytes_read"] += rows * self._kv_row_bytes
                self.stats["kv_bytes_read_live"] += rows * self._kv_row_bytes
                self.stats["kv_bytes_read_device"] += per_device_kv_bytes(
                    rows * self._kv_row_bytes, self.tp)
            tok_dev, ok_dev, self.pool.cache = self._decode(
                self.params, jnp.asarray(self._tokens),
                jnp.asarray(self.pool.lens), self.pool.cache,
                self._next_key(), jnp.asarray(self._temps),
                jnp.asarray(self._topks), bt,
                use_topk=bool(self._topks.any()))
        with span("engine.decode.wait"):
            next_tok = np.asarray(tok_dev)
            ok = np.array(ok_dev)  # writable: the fault hook may flip a row
        with span("engine.commit"):
            faults = self.faults
            if faults is not None and faults.fires(self._step_idx,
                                                   "nan_logits"):
                slots_live = sorted(self.sched.active)
                victim = slots_live[faults.choose(len(slots_live))]
                faults.record(self._step_idx, "nan_logits", victim)
                ok[victim] = False
            now = self._clock()
            self.stats["decode_steps"] += 1

            for slot, req in list(self.sched.active.items()):
                if not ok[slot]:
                    # containment: fail ONLY the poisoned row — its token
                    # is garbage, so nothing is emitted and the slot retires
                    req.error = "non-finite logits at decode"
                    self._release(slot)
                    finished.append(self.sched.retire(slot, FAILED))
                    self.stats["failed"] += 1
                    continue
                tok = int(next_tok[slot])
                req.generated.append(tok)
                req.token_times.append(now)
                self.pool.advance(slot)
                self._tokens[slot, 0] = tok
                if req.is_finished():
                    self._release(slot)
                    finished.append(self.sched.retire(slot))
            self._finish_step(t_step)
        return finished

    def _admit(self) -> Tuple[List[Request], List, bool]:
        """The step's admission phase: fault hooks, deadline expiry, the
        scheduler's pick, page reservation and page-pressure preemption.
        Returns the requests that reached a terminal status, the admitted
        ``(req, slot)`` pairs, and whether the FCFS head stalled on
        pages."""
        finished: List[Request] = []
        faults = self.faults
        if faults is not None:
            faults.maybe_sleep(self._step_idx)
            if faults.fires(self._step_idx, "shard_skew"):
                # one shard running slow: SPMD programs are lockstep (every
                # collective is a barrier), so the WHOLE step stalls for
                # the skewed shard's delay — an engine-level sleep is the
                # exact observable effect. `choose` records which shard
                # skewed so tests can assert the victim distribution.
                shard = faults.choose(max(self.tp, 1))
                faults.record(self._step_idx, "shard_skew", shard)
                faults.sleep(faults.arg(self._step_idx, "shard_skew")
                             or 0.02)
            if faults.fires(self._step_idx, "cancel"):
                live = sorted([r.rid for r in self.sched.active.values()]
                              + [r.rid for r in self.sched.waiting])
                if live:
                    rid = live[faults.choose(len(live))]
                    faults.record(self._step_idx, "cancel", rid)
                    req = self.cancel(rid)
                    if req is not None:
                        finished.append(req)
        finished.extend(self._expire_deadlines())

        admitted = self.sched.admit(self.ec.max_admit_per_step) \
            if self._should_admit() else []
        stalled = False
        if admitted and self.paged:
            # page-budget admission control: each admission reserves its
            # worst-case page count (prompt + max_new_tokens) so a running
            # request can never strand without a page mid-decode. Strict
            # FCFS — the first request that doesn't fit requeues itself and
            # everything behind it (reverse order restores queue order),
            # even if a later prefix-hit request would have fit in the
            # leftover budget: sharing must not let newcomers starve an
            # earlier stalled request. With prefix sharing on, admission
            # first adopts each prompt's cached full-page prefix and only
            # reserves the uncached-suffix budget.
            fit = len(admitted)
            if faults is not None and faults.fires(self._step_idx,
                                                   "page_alloc"):
                # injected allocator failure: the whole admission wave
                # behaves as if the pool were exhausted (stall path)
                faults.record(self._step_idx, "page_alloc")
                fit = 0
            else:
                for i, (req, slot) in enumerate(admitted):
                    # folded preemption tokens are part of the prompt now,
                    # but only max_new_tokens - folded generations remain:
                    # the total is invariant across folds
                    total = (req.prompt_len - req.folded
                             + req.max_new_tokens + self._headroom())
                    if self.prefix_cache:
                        hit = self.pool.admit_prefix(slot, req.prompt, total)
                        if hit is None:
                            fit = i
                            break
                        req.prefix_hit = hit
                        self.stats["pages_shared"] += -(-hit
                                                        // self.pool.page_size)
                    elif not self.pool.reserve(slot, total):
                        fit = i
                        break
            for req, slot in reversed(admitted[fit:]):
                self.sched.requeue(slot)
                self.stats["page_stalls"] += 1
            stalled = fit == 0
            admitted = admitted[:fit]
        if stalled and self.ec.preempt_after_stalls > 0:
            # page-pressure preemption: when the FCFS head has stalled past
            # the defer budget and slots are still running, evict the
            # youngest running request so the head can seat next step
            self._stall_steps += 1
            if (self._stall_steps > self.ec.preempt_after_stalls
                    and self.sched.active):
                self._preempt_youngest()
                self._stall_steps = 0
        elif admitted or not self.sched.waiting:
            self._stall_steps = 0
        return finished, admitted, stalled

    def _finish_step(self, t_start: float) -> None:
        """End-of-step bookkeeping shared by every return path: mirror pool
        counters and feed the step duration to the watchdog."""
        self._sync_pool_stats()
        dt = self._clock() - t_start
        if self._watchdog is not None:
            self._watchdog.record(dt)
            self.stats["watchdog_slow_steps"] = self._watchdog.slow_steps
            self.stats["step_time_ewma"] = self._watchdog.ewma
        # admission-estimator calibration (survives reset_stats; warmup
        # clears it so compile steps never seed the estimate)
        self._step_time = (dt if self._step_time <= 0
                           else 0.8 * self._step_time + 0.2 * dt)

    def _release(self, slot: int) -> None:
        self.pool.release(slot)
        if self.spec:
            self.drafter.release(slot)

    def _expire_deadlines(self) -> List[Request]:
        """Retire every live request whose deadline has passed (TIMEOUT)."""
        out: List[Request] = []
        now = self._clock()
        for req in list(self.sched.waiting):
            if req.deadline_s > 0 and now > req.submit_time + req.deadline_s:
                out.append(self.sched.drop_waiting(
                    req, TIMEOUT, "deadline expired while queued"))
                self.stats["timeouts"] += 1
                self.stats["timeouts_waiting"] += 1
        for slot, req in list(self.sched.active.items()):
            if req.deadline_s > 0 and now > req.submit_time + req.deadline_s:
                req.error = "deadline expired mid-decode"
                self._release(slot)
                out.append(self.sched.retire(slot, TIMEOUT))
                self.stats["timeouts"] += 1
                self.stats["timeouts_running"] += 1
        for rid, req in list(self.sched.paused.items()):
            if req.deadline_s > 0 and now > req.submit_time + req.deadline_s:
                out.append(self.sched.drop_paused(
                    rid, TIMEOUT, "deadline expired while paused"))
                self.stats["timeouts"] += 1
                self.stats["timeouts_running"] += 1
        return out

    @staticmethod
    def _fold(req: Request) -> None:
        """Fold a request's generated-so-far tokens into its prompt so a
        later re-prefill replays them and samples exactly the next token
        (bit-identical under greedy). The reservation total
        ``prompt_len - folded + max_new_tokens`` is invariant across folds,
        so a folded request always re-fits eventually. Shared by
        page-pressure preemption and crash :meth:`recover`."""
        new = req.generated[req.folded:]
        if new:
            req.prompt = np.concatenate(
                [req.prompt, np.asarray(new, np.int32)])
            req.folded = len(req.generated)

    def _preempt_youngest(self) -> Request:
        """Page-pressure eviction: fold the victim's generated tokens into
        its prompt, release its slot + pages, and requeue it behind the
        stalled FCFS head. The victim is the youngest running request of
        the LOWEST priority tier — a high-priority request is evicted only
        when nothing cheaper is running."""
        slot, req = max(self.sched.active.items(),
                        key=lambda kv: (-kv[1].priority, kv[1].admit_time,
                                        kv[1].rid))
        self._fold(req)
        self._release(slot)
        self.stats["preemptions"] += 1
        return self.sched.preempt(slot)

    def check_conservation(self) -> None:
        """Assert nothing leaked once the engine drains: every slot free,
        no live requests, and (paged) every non-null page accounted for
        with consistent refcounts. Chaos tests call this after mixed-fault
        runs; it is cheap enough to call in benches too."""
        with self._elock:
            assert (not self.sched.active and not self.sched.waiting
                    and not self.sched.paused), \
                "check_conservation() needs a drained engine"
            assert self.sched.free_slots() == self.ec.n_slots, "leaked slots"
            if self.paged:
                self.pool.check_consistency()
                idle = self.pool.idle_pages()
                assert idle == self.pool.n_pages - 1, \
                    f"leaked {self.pool.n_pages - 1 - idle} KV pages"
            else:
                assert int(np.asarray(self.pool.lens).sum()) == 0, \
                    "leaked slot lengths"
            if self.spec and hasattr(self.drafter, "pool"):
                assert int(np.asarray(self.drafter.pool.lens).sum()) == 0, \
                    "leaked drafter slot lengths"

    def _prepare_paged_writes(self, write_lens: Dict[int, int],
                              extra: int) -> jax.Array:
        """Page bookkeeping shared by plain decode (each slot writes one
        K/V row: ``write_len = len + 1``) and the speculative verify
        dispatch (``len + suffix``) — decode really is the suffix-1 case.

        Alloc-on-advance: every page a slot's write frontier will touch
        must exist before the dispatch (drawn from the admission-time
        reservation, never from thin air). With prefix sharing the page
        holding the first written position (the current length) must also
        be PRIVATE — admission CoW already guarantees that for the
        engine's own flow, so this is a cheap invariant check that
        batches any stragglers; pages past it were just drawn fresh.
        Returns the device block tables at the pow2 width covering
        ``len + extra`` and accounts the step's KV read traffic."""
        cow: List = []
        for slot, wlen in write_lens.items():
            self.pool.ensure(slot, wlen)
            if self.prefix_cache:
                pair = self.pool.ensure_writable(
                    slot, int(self.pool.lens[slot]))
                if pair is not None:
                    cow.append(pair)
        if cow:
            src, dst = zip(*cow)
            self.pool.copy_pages(np.asarray(src), np.asarray(dst))
        bt = self.pool.device_tables(self.pool.table_width(extra=extra))
        step_bytes = (bt.shape[1] * self.ec.page_size * self.ec.n_slots
                      * self._kv_row_bytes)
        self.stats["kv_bytes_read"] += step_bytes
        self.stats["kv_bytes_read_live"] += (self.pool.live_page_rows()
                                             * self._kv_row_bytes)
        # under a mesh each device reads only its Hkv slice of every page
        self.stats["kv_bytes_read_device"] += per_device_kv_bytes(
            step_bytes, self.tp)
        return bt

    def _attn_steps(self, n_cols: int) -> Dict[str, int]:
        """Grid steps per layer of this decode's ``paged_attention`` call
        over a table ``n_cols`` wide, and those that copy a live page:
        every slot attends over ``len + 1`` positions (a free slot's one
        position lies in the null page), from the kernel's own block-size
        rule."""
        from repro.kernels.paged_decode_attention import attention_grid_steps
        cfg = self.cfg
        itemsize = 1 if cfg.kv_dtype == "int8" else cfg.c_dtype.itemsize
        grid, live = attention_grid_steps(
            self.pool.lens + 1, self.ec.page_size,
            cfg.num_kv_heads // self.tp, cfg.head_dim, itemsize,
            cfg.num_heads // cfg.num_kv_heads, n_cols)
        return {"attn_grid_steps": grid, "attn_live_steps": live}

    def _spec_step(self) -> List[Request]:
        """One draft→verify→accept iteration over every live slot.

        The drafter proposes up to ``spec_k`` tokens per slot; ONE
        ``prefill_append`` dispatch scores the pending token plus all
        drafts against the paged prefix (suffix row j's logits are the
        target's distribution for position ``len + j + 1``); acceptance
        keeps the longest agreeing draft prefix and always emits one more
        token from the target's own row, so each step commits 1..spec_k+1
        tokens with exactly the plain-decode output distribution.
        Rejected drafts roll back by truncating the pool to the committed
        frontier — the pages they were written into were allocated this
        step and never shared, so they return straight to the slot's
        reservation."""
        from repro.serving.speculative import accept_draft, accept_greedy
        active = sorted(self.sched.active.items())
        tlens = self.pool.lens.copy()
        faults = self.faults
        try:
            if faults is not None and faults.fires(self._step_idx,
                                                   "drafter"):
                faults.record(self._step_idx, "drafter")
                raise RuntimeError("injected drafter failure")
            proposals = self.drafter.propose(active, tlens, self.ec.spec_k,
                                             self._rng)
        except Exception:
            # drafter containment: a failed propose degrades this round to
            # a zero-draft verify — exactly a plain decode step. A drafter
            # whose internal state desynced (DraftModel asserts catch-up ≤
            # 1) keeps failing here, so the engine permanently degrades to
            # 1-token steps instead of crashing; output is unchanged.
            self.stats["drafter_failures"] += 1
            proposals = {slot: ([], None) for slot, _ in active}
        s_max = self.ec.spec_k + 1
        toks = np.zeros((self.ec.n_slots, s_max), np.int32)
        plens = np.zeros((self.ec.n_slots,), np.int32)
        slens = np.zeros((self.ec.n_slots,), np.int32)
        for slot, req in active:
            seq = [int(self._tokens[slot, 0])] + list(proposals[slot][0])
            toks[slot, :len(seq)] = seq
            plens[slot] = tlens[slot]
            slens[slot] = len(seq)
        bt = self._prepare_paged_writes(
            {slot: int(tlens[slot]) + int(slens[slot])
             for slot, _ in active}, extra=s_max)
        # all-greedy steps ship (B, S) argmax rows instead of (B, S, V)
        # logits — at real vocab sizes that is the difference between a
        # few KB and a few MB on the device-host link every step
        greedy_only = all(req.temperature <= 0 for _, req in active)
        out_dev, ok_dev, self.pool.cache = self._verify(
            self.params, jnp.asarray(toks), jnp.asarray(plens),
            jnp.asarray(slens), self.pool.cache, bt,
            greedy_only=greedy_only)
        out = np.asarray(out_dev)
        ok = np.array(ok_dev)      # writable: the fault hook may flip a row
        if faults is not None and faults.fires(self._step_idx, "nan_logits"):
            victim = active[faults.choose(len(active))][0]
            faults.record(self._step_idx, "nan_logits", victim)
            ok[victim] = False
        now = self._clock()
        self.stats["decode_steps"] += 1
        self.stats["spec_steps"] += 1

        finished: List[Request] = []
        for slot, req in active:
            if not ok[slot]:
                # containment: every token this verify scored for the slot
                # is suspect — emit nothing, fail the request, release its
                # pages (including the draft rows past the frontier)
                req.error = "non-finite logits at verify"
                self._release(slot)
                finished.append(self.sched.retire(slot, FAILED))
                self.stats["failed"] += 1
                continue
            props, qrows = proposals[slot]
            n = len(props)
            if greedy_only:
                a, follow = accept_greedy(out[slot], props)
            else:
                a, follow = accept_draft(out[slot, :n + 1], props, qrows,
                                         req.temperature, req.top_k,
                                         self._rng)
            committed = 0
            for tok in props[:a] + [follow]:
                req.generated.append(int(tok))
                req.token_times.append(now)
                committed += 1
                if req.is_finished():
                    break
            # acceptance stats count drafts actually EMITTED: a request
            # finishing mid-block discards the accepted tail, and tokens
            # rolled back by truncate must not inflate the rate
            a_committed = min(committed, a)
            self.stats["draft_proposed"] += n
            self.stats["draft_accepted"] += a_committed
            self.stats["accepted_hist"][a_committed] += 1
            self._tokens[slot, 0] = req.generated[-1]
            new_len = int(tlens[slot]) + committed
            self.pool.truncate(slot, new_len)
            self.drafter.rollback(slot, new_len)
            if req.is_finished():
                self._release(slot)
                finished.append(self.sched.retire(slot))
        return finished

    # -- convenience -------------------------------------------------------

    def reset_stats(self) -> None:
        with self._elock:
            self.stats.clear()
            self.stats.update(decode_steps=0, prefills=0, prefill_rows=0,
                              page_stalls=0, kv_bytes_read=0,
                              kv_bytes_read_live=0, kv_bytes_read_device=0,
                              slot_occupancy=[],
                              prefix_hit_tokens=0, pages_shared=0,
                              cow_copies=0, evictions=0, pages_allocated=0,
                              spec_steps=0, draft_proposed=0,
                              draft_accepted=0,
                              accepted_hist=[0] * (self.ec.spec_k + 1),
                              preemptions=0, shed=0, rejected=0, timeouts=0,
                              cancelled=0, failed=0, drafter_failures=0,
                              recoveries=0, watchdog_slow_steps=0,
                              step_time_ewma=0.0,
                              slo_rejected=0, quota_rejected=0,
                              timeouts_waiting=0, timeouts_running=0,
                              wasted_prefill_tokens=0, paused=0, resumed=0,
                              tenants={})
            # fresh watchdog per reset: warmup's compile-heavy steps must
            # not seed the EWMA the measured window is judged against
            self._watchdog = (
                StepWatchdog(threshold=self.ec.watchdog_threshold)
                if self.ec.watchdog_threshold > 0 else None)
            if self.paged:
                self.pool.reset_stats()
                self._sync_pool_stats()

    def _sync_pool_stats(self) -> None:
        """Mirror the allocator's counters (they tick deep inside page
        allocation / CoW) and its page gauges (``kv_pages_in_use``,
        ``kv_pages_reserved``, ``kv_pages_free``) into the reported stats
        dict — the pool is the single source of truth for page-level
        events."""
        if self.paged:
            for key in ("evictions", "pages_allocated", "cow_copies"):
                self.stats[key] = self.pool.stats[key]
            self.stats.update(self.pool.page_gauges())

    def warmup(self, prompt_lens: Sequence[int], gen: int = 2,
               suffix_lens: Optional[Sequence[int]] = None) -> None:
        """Compile every (prefill bucket × admission row tier) program plus
        the decode/sample programs with throwaway requests, then wipe the
        bookkeeping — so measured traffic doesn't pay jit compilation
        inside the timed window. With prefix sharing on, the suffix-only
        ``prefill_append`` programs are compiled too (suffix buckets ×
        row tiers; ``suffix_lens`` narrows the bucket set — default: the
        prompt buckets plus the minimum bucket, since a hit can shrink any
        prompt to a tiny suffix), and the prefix index populated by the
        throwaway prompts is dropped so measured traffic starts cold."""
        assert not self.sched.has_work(), "warmup() needs an idle engine"
        buckets = sorted({self._bucket(max(1, int(p))) for p in prompt_lens})
        lens = [min(b, self.ec.capacity - gen - self._headroom())
                for b in buckets]
        for l in lens:
            for tier in self._row_tiers():
                self.generate([np.zeros((l,), np.int32)] * tier,
                              max_new_tokens=gen)
                if self.prefix_cache:
                    # drop the throwaway prompts' index entries NOW, not
                    # just at the end: otherwise every generate() after
                    # the first hits the cache and takes the append path,
                    # and the COLD prefill programs for the remaining
                    # (bucket × tier) combos never compile — measured
                    # traffic would pay them inside the timed window
                    self.pool.reset_prefix()
        if self.prefix_cache:
            if suffix_lens is None:
                suffix_lens = buckets
            # a prefix hit can shrink any prompt to any suffix length, and
            # an admission group's bucket is the max over its members — so
            # compile EVERY pow2 bucket up to the largest possible suffix
            # (O(log capacity) × O(log n_slots) programs, warmup-only)
            top = max(self._bucket(max(1, int(s))) for s in suffix_lens)
            sbuckets, sb = [], self.ec.min_bucket
            while sb <= top:
                sbuckets.append(sb)
                sb *= 2
            zeros = jnp.zeros((self.ec.n_slots,), jnp.float32)
            for sb in sbuckets:
                for tier in self._row_tiers():
                    for w in self._pow2_widths():
                        # all-zero tables route every write into the null
                        # page; greedy sampling matches the cold-prefill
                        # warmup's compiled sample path. Admission buckets
                        # the table width to a power of two, so every
                        # (suffix bucket × row tier × width) program must
                        # exist before measured traffic.
                        _, _, self.pool.cache = self._append(
                            self.params,
                            jnp.zeros((tier, sb), jnp.int32),
                            jnp.zeros((tier,), jnp.int32),
                            jnp.ones((tier,), jnp.int32),
                            self.pool.cache,
                            jnp.zeros((tier, w), jnp.int32),
                            self._next_key(), zeros[:tier],
                            zeros[:tier].astype(jnp.int32), use_topk=False)
            self.pool.reset_prefix()
        if self.paged:
            # compile the decode-path program for every block-table width
            # the pow2 bucketing can produce — bucket growth mid-traffic
            # must not pay jit inside the measured window. All-zero tables
            # route the throwaway writes into the null page. In
            # speculative mode every step is a verify dispatch, so that
            # program (spec_k+1 suffix rows, host-side sampling) is the
            # one compiled per width instead of the fused decode+sample.
            widths = self._pow2_widths()
            zeros = jnp.zeros((self.ec.n_slots,), jnp.float32)
            lens0 = jnp.zeros((self.ec.n_slots,), jnp.int32)
            if self.spec:
                toks = jnp.zeros((self.ec.n_slots, self.ec.spec_k + 1),
                                 jnp.int32)
                for w in widths:
                    bt = jnp.zeros((self.ec.n_slots, w), jnp.int32)
                    for greedy_only in (True, False):  # both static paths
                        _, _, self.pool.cache = self._verify(
                            self.params, toks, lens0, lens0,
                            self.pool.cache, bt, greedy_only=greedy_only)
                if hasattr(self.drafter, "warmup"):
                    # warmup traffic is all-greedy; the drafter's
                    # sampled-path program must not jit mid-traffic
                    self.drafter.warmup()
            else:
                toks = jnp.zeros((self.ec.n_slots, 1), jnp.int32)
                for w in widths:
                    bt = jnp.zeros((self.ec.n_slots, w), jnp.int32)
                    for use_topk in (False, True):  # both sample paths
                        _, _, self.pool.cache = self._decode(
                            self.params, toks, lens0, self.pool.cache,
                            self._next_key(), zeros,
                            zeros.astype(jnp.int32), bt, use_topk=use_topk)
        self.sched.finished.clear()
        # warmup steps paid jit compiles — worthless as admission-estimator
        # calibration; start the EWMA fresh from measured traffic
        self._step_time = 0.0
        self.reset_stats()

    def run(self) -> List[Request]:
        """Drain: step until queue and slots are empty; finished requests in
        completion order."""
        done: List[Request] = []
        while self.sched.has_work():
            done.extend(self.step())
        return done

    def generate(self, prompts: Sequence[Sequence[int]], *,
                 max_new_tokens: int = 16, temperature: float = 0.0,
                 top_k: int = 0, eos_id: Optional[int] = None
                 ) -> List[List[int]]:
        """Batch convenience: submit all prompts, drain, return generated
        token lists in submission order."""
        rids = [self.submit(p, max_new_tokens=max_new_tokens,
                            temperature=temperature, top_k=top_k,
                            eos_id=eos_id) for p in prompts]
        by_rid = {r.rid: r for r in self.run()}
        # requests rejected at submit never pass through run(); they come
        # back as empty generations rather than a KeyError
        return [by_rid[rid].generated if rid in by_rid else []
                for rid in rids]
