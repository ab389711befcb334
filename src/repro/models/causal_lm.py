"""Generic causal LM: one stack driver covers dense (llama/qwen/pixtral),
MoE (deepseek/llama4), hybrid (jamba), and SSM (rwkv6) families.

The per-layer plan ``(mixer, ffn)`` is derived statically from the config and
compressed into repeating *segments* that are scanned with stacked params —
HLO stays O(period), not O(depth) (126-layer llama3-405b compiles as one
scanned block). Decode threads recurrent caches through the same segments.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.core.sparse_linear import linear_apply, linear_init
from repro.models import layers as L
from repro.models import mixers as M
from repro.models import moe as MOE
from repro.runtime import partitioning as part
from repro.runtime.collectives import maybe_gather

Params = Dict[str, Any]


def _head_logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    """lm_head projection; under tensor parallelism (``cfg.tp_axis`` set
    inside the sharded engine's shard_map) the head is column-parallel
    over vocab, so re-replicate the logits before sampling — every shard
    then argmaxes/samples the identical full row."""
    logits = linear_apply(params["lm_head"], x, impl=cfg.kernel_impl)
    return maybe_gather(logits, cfg.vocab_size, cfg.tp_axis)


# ---------------------------------------------------------------------------
# Layer plan / segmentation
# ---------------------------------------------------------------------------


def layer_plan(cfg: ModelConfig) -> List[Tuple[str, str]]:
    plan = []
    for i in range(cfg.num_layers):
        if cfg.family == "ssm":
            plan.append(("rwkv", "rwkv_cm"))
            continue
        if cfg.attn_period:
            mixer = "attn" if i % cfg.attn_period == cfg.attn_offset else "mamba"
        else:
            mixer = "attn"
        ffn = "mlp"
        if cfg.num_experts and i >= cfg.moe_first_dense:
            j = i - cfg.moe_first_dense
            if cfg.moe_every <= 1 or j % cfg.moe_every == cfg.moe_every - 1:
                ffn = "moe"
        plan.append((mixer, ffn))
    return plan


def _find_period(plan: List[Tuple[str, str]]) -> int:
    n = len(plan)
    for p in range(1, n + 1):
        if n % p == 0 and plan == plan[:p] * (n // p):
            return p
    return n


@dataclasses.dataclass(frozen=True)
class StackPlan:
    prefix: Tuple[Tuple[str, str], ...]    # unstacked leading layers
    pattern: Tuple[Tuple[str, str], ...]   # repeating period
    repeats: int


def stack_plan(cfg: ModelConfig) -> StackPlan:
    plan = layer_plan(cfg)
    n_prefix = cfg.moe_first_dense if cfg.num_experts else 0
    prefix, rest = plan[:n_prefix], plan[n_prefix:]
    if not rest:
        return StackPlan(tuple(prefix), (), 0)
    if not cfg.scan_layers:
        return StackPlan(tuple(plan), (), 0)
    p = _find_period(rest)
    return StackPlan(tuple(prefix), tuple(rest[:p]), len(rest) // p)


# ---------------------------------------------------------------------------
# Per-layer init / apply / cache
# ---------------------------------------------------------------------------


def _mixer_init(key, kind: str, cfg: ModelConfig) -> Params:
    if kind == "attn":
        return L.attention_init(key, cfg.d_model, cfg.num_heads,
                                cfg.num_kv_heads, cfg.head_dim,
                                qkv_bias=cfg.qkv_bias, dtype=cfg.p_dtype)
    if kind == "mamba":
        return M.mamba_init(key, cfg)
    if kind == "rwkv":
        return M.rwkv_tm_init(key, cfg)
    raise ValueError(kind)


def _ffn_init(key, kind: str, cfg: ModelConfig) -> Params:
    if kind == "mlp":
        return L.swiglu_init(key, cfg.d_model, cfg.d_ff, dtype=cfg.p_dtype)
    if kind == "moe":
        return MOE.moe_init(key, cfg)
    if kind == "rwkv_cm":
        return M.rwkv_cm_init(key, cfg)
    raise ValueError(kind)


def layer_init(key, kinds: Tuple[str, str], cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "norm1": L.rmsnorm_init(cfg.d_model, cfg.p_dtype),
        "mixer": _mixer_init(k1, kinds[0], cfg),
        "norm2": L.rmsnorm_init(cfg.d_model, cfg.p_dtype),
        "ffn": _ffn_init(k2, kinds[1], cfg),
    }


def _mixer_cache_init(kind: str, cfg: ModelConfig, batch: int, capacity: int,
                      kv_pages: int = 0, page_size: int = 0):
    if kind == "attn":
        quant = cfg.kv_dtype == "int8"
        kv_dt = jnp.int8 if quant else cfg.c_dtype
        if page_size > 0:
            # block-paged layout: one shared page pool per layer, indexed
            # by per-slot block tables at decode (page 0 reserved as the
            # null sink for pad/inactive writes). kv_dtype="int8" stores
            # quantized codes plus sibling per-row-per-head scale pools
            # that share the page index space (so page copies / frees /
            # table lookups cover data and scales together). Head-major,
            # so a (page, kv-head) kernel block is a full (page_size, D).
            shape = (kv_pages, cfg.num_kv_heads, page_size, cfg.head_dim)
            c = {"k": jnp.zeros(shape, kv_dt), "v": jnp.zeros(shape, kv_dt)}
            if quant:
                c["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
                c["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
            return c
        shape = (batch, capacity, cfg.num_kv_heads, cfg.head_dim)
        c = {"k": jnp.zeros(shape, kv_dt), "v": jnp.zeros(shape, kv_dt)}
        if quant:
            c["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
            c["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        return c
    if kind == "mamba":
        return M.mamba_init_cache(cfg, batch, cfg.c_dtype)
    if kind == "rwkv":
        return M.rwkv_tm_init_cache(cfg, batch, cfg.c_dtype)
    raise ValueError(kind)


def _ffn_cache_init(kind: str, cfg: ModelConfig, batch: int):
    if kind == "rwkv_cm":
        return M.rwkv_cm_init_cache(cfg, batch, cfg.c_dtype)
    return {}


def layer_cache_init(kinds: Tuple[str, str], cfg: ModelConfig, batch: int,
                     capacity: int, kv_pages: int = 0, page_size: int = 0):
    return {
        "mixer": _mixer_cache_init(kinds[0], cfg, batch, capacity,
                                   kv_pages, page_size),
        "ffn": _ffn_cache_init(kinds[1], cfg, batch),
    }


def layer_apply(
    kinds: Tuple[str, str], lp: Params, x: jax.Array, cfg: ModelConfig, *,
    positions: jax.Array, cache: Optional[Params] = None,
    cache_len: Optional[jax.Array] = None,
    block_tables: Optional[jax.Array] = None,
    suffix_len: Optional[jax.Array] = None,
    token_mask: Optional[jax.Array] = None, want_cache: bool = False,
) -> Tuple[jax.Array, Optional[Params]]:
    """One transformer/SSM layer; decode when ``cache`` is provided.

    ``token_mask`` (B, S) bool marks tokens allowed to claim MoE expert
    capacity (None → all); attention/MLP/recurrent paths ignore it — they
    are row-independent, only capacity-factor routing couples tokens.
    ``suffix_len`` switches a paged multi-token call into prefill-append
    (see ``attention_apply``).
    """
    mixer_kind, ffn_kind = kinds
    impl = cfg.kernel_impl
    x = part.act(x, "batch", "seq_sp", "embed")
    h = L.rmsnorm(lp["norm1"], x, cfg.norm_eps)
    new_cache: Params = {}

    if mixer_kind == "attn":
        out, kv = L.attention_apply(
            lp["mixer"], h, n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads,
            head_dim=cfg.head_dim, positions=positions,
            rope_theta=cfg.rope_theta, causal=True,
            cache=(cache["mixer"] if cache is not None else None),
            cache_len=cache_len, block_tables=block_tables,
            suffix_len=suffix_len, attn_impl=cfg.attn_impl,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk, impl=impl,
            tp_axis=cfg.tp_axis)
        if cache is not None or want_cache:
            if "k_scale" in kv:
                # quantized pools/caches come back from attention_apply in
                # their final layout (codes + scales) — pass through
                new_cache["mixer"] = kv
            elif cfg.kv_dtype == "int8":
                # fresh prefill rows: quantize on emission so the cache
                # the slot pool inserts already matches the int8 + scale
                # leaf structure of init_cache
                from repro.kernels.quant import quantize_rows
                kc, ks = quantize_rows(kv["k"])
                vc, vs = quantize_rows(kv["v"])
                new_cache["mixer"] = {"k": kc, "v": vc,
                                      "k_scale": ks, "v_scale": vs}
            else:
                new_cache["mixer"] = {
                    "k": kv["k"].astype(cfg.c_dtype),
                    "v": kv["v"].astype(cfg.c_dtype)}
    elif mixer_kind == "mamba":
        if cache is not None:
            out, mc = M.mamba_apply_step(lp["mixer"], h, cache["mixer"], cfg, impl)
            new_cache["mixer"] = mc
        elif want_cache:
            out, mc = M.mamba_apply_seq(lp["mixer"], h, cfg, impl,
                                        return_state=True)
            new_cache["mixer"] = mc
        else:
            out = M.mamba_apply_seq(lp["mixer"], h, cfg, impl)
    elif mixer_kind == "rwkv":
        if cache is not None:
            out, rc = M.rwkv_tm_apply_step(lp["mixer"], h, cache["mixer"], cfg, impl)
            new_cache["mixer"] = rc
        elif want_cache:
            out, rc = M.rwkv_tm_apply_seq(lp["mixer"], h, cfg, impl,
                                          return_state=True)
            new_cache["mixer"] = rc
        else:
            out = M.rwkv_tm_apply_seq(lp["mixer"], h, cfg, impl)
    else:
        raise ValueError(mixer_kind)
    # constrain the block output to the residual's (seq-parallel) layout
    # BEFORE the add so GSPMD emits reduce-scatter, not all-reduce + slice
    # (perf iteration C4)
    out = part.act(out, "batch", "seq_sp", "embed")
    x = x + out

    h2 = L.rmsnorm(lp["norm2"], x, cfg.norm_eps)
    if ffn_kind == "mlp":
        with jax.named_scope("projections"):
            out2 = L.swiglu_apply(lp["ffn"], h2, impl, tp_axis=cfg.tp_axis)
    elif ffn_kind == "moe":
        out2 = MOE.moe_apply(lp["ffn"], h2, cfg, impl,
                             token_mask=token_mask)
    elif ffn_kind == "rwkv_cm":
        if cache is not None:
            out2, cc = M.rwkv_cm_apply_step(lp["ffn"], h2, cache["ffn"], cfg, impl)
            new_cache["ffn"] = cc
        else:
            out2 = M.rwkv_cm_apply_seq(lp["ffn"], h2, cfg, impl)
            if want_cache:
                new_cache["ffn"] = {"shift": h2[:, -1, :].astype(cfg.c_dtype)}
    else:
        raise ValueError(ffn_kind)
    out2 = part.act(out2, "batch", "seq_sp", "embed")
    x = x + out2
    if cache is not None or want_cache:
        new_cache.setdefault("ffn", {})  # structural parity with init_cache
        return x, new_cache
    return x, None


# ---------------------------------------------------------------------------
# Whole-model init / forward / decode
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    sp = stack_plan(cfg)
    keys = jax.random.split(key, 4)
    params: Params = {
        "embed": L.embed_init(keys[0], cfg.vocab_size, cfg.d_model, cfg.p_dtype),
        "final_norm": L.rmsnorm_init(cfg.d_model, cfg.p_dtype),
        "lm_head": linear_init(keys[1], cfg.d_model, cfg.vocab_size,
                               dtype=cfg.p_dtype),
        "prefix": [
            layer_init(jax.random.fold_in(keys[2], i), kinds, cfg)
            for i, kinds in enumerate(sp.prefix)
        ],
    }
    if sp.repeats:
        def init_repeat(k):
            ks = jax.random.split(k, len(sp.pattern))
            return [layer_init(ks[i], kinds, cfg)
                    for i, kinds in enumerate(sp.pattern)]
        rkeys = jax.random.split(keys[3], sp.repeats)
        params["stack"] = jax.vmap(init_repeat)(rkeys)
    else:
        params["stack"] = []
    return params


def _embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  image_embeds: Optional[jax.Array]) -> jax.Array:
    h = L.embed(params["embed"], tokens).astype(cfg.act_dtype)
    if cfg.num_image_tokens and image_embeds is not None:
        p = image_embeds.shape[1]
        h = jnp.concatenate([image_embeds.astype(h.dtype), h[:, p:]], axis=1)
    return part.act(h, "batch", "seq_sp", "embed")


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            image_embeds: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence forward → logits (train / eval)."""
    sp = stack_plan(cfg)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = _embed_tokens(cfg, params, tokens, image_embeds)

    for kinds, lp in zip(sp.prefix, params["prefix"]):
        x, _ = layer_apply(kinds, lp, x, cfg, positions=positions)

    if sp.repeats:
        def body(x, rep_params):
            for kinds, lp in zip(sp.pattern, rep_params):
                x, _ = layer_apply(kinds, lp, x, cfg, positions=positions)
            return x, None
        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["stack"])

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _head_logits(cfg, params, x)
    return part.act(logits, "batch", "seq", "vocab")


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array]
            ) -> jax.Array:
    logits = forward(cfg, params, batch["tokens"],
                     image_embeds=batch.get("image_embeds"))
    return L.cross_entropy(logits, batch["targets"], batch.get("mask"))


def init_cache(cfg: ModelConfig, batch: int, capacity: int, *,
               kv_pages: int = 0, page_size: int = 0) -> Params:
    """Decode cache pytree, stacked to mirror the param layout.

    With ``page_size > 0`` attention K/V leaves become a shared page pool
    ``(kv_pages, Hkv, page_size, D)`` per layer (block tables supplied to
    ``decode_step`` map slots onto pages); recurrent-state leaves keep the
    per-slot batch layout either way.
    """
    sp = stack_plan(cfg)
    cache: Params = {
        "prefix": [layer_cache_init(kinds, cfg, batch, capacity,
                                    kv_pages, page_size)
                   for kinds in sp.prefix],
    }
    if sp.repeats:
        one = lambda _: [layer_cache_init(kinds, cfg, batch, capacity,
                                          kv_pages, page_size)
                         for kinds in sp.pattern]
        cache["stack"] = jax.vmap(one)(jnp.arange(sp.repeats))
    else:
        cache["stack"] = []
    return cache


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Params, cache_len: jax.Array,
                block_tables: Optional[jax.Array] = None,
                token_mask: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Params]:
    """One serving step: tokens (B, 1) + cache → (logits (B, 1, V), cache').

    ``cache_len`` is a scalar (uniform batch) or a (B,) vector for ragged
    continuous-batching decode: slot b writes its K/V at position
    ``cache_len[b]`` and attends to its own history only.

    ``block_tables`` (B, n_cols) switches attention layers to the paged
    cache layout: KV bytes touched per step scale with the table width the
    caller hands over (bucketed to the longest live slot) instead of the
    provisioned capacity.

    ``token_mask`` (B, 1) marks rows allowed to claim MoE expert capacity
    — the engine passes ``lens > 0`` so free-slot garbage rows cannot
    evict real tokens (None → all rows route, the single-request path).
    """
    sp = stack_plan(cfg)
    b = tokens.shape[0]
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (b,))
    positions = cache_len[:, None]
    x = _embed_tokens(cfg, params, tokens, None)

    new_prefix = []
    for kinds, lp, c in zip(sp.prefix, params["prefix"], cache["prefix"]):
        x, nc = layer_apply(kinds, lp, x, cfg, positions=positions,
                            cache=c, cache_len=cache_len,
                            block_tables=block_tables,
                            token_mask=token_mask)
        new_prefix.append(nc)

    new_stack = cache["stack"]
    if sp.repeats:
        def body(x, inp):
            rep_params, rep_cache = inp
            ncs = []
            for kinds, lp, c in zip(sp.pattern, rep_params, rep_cache):
                x, nc = layer_apply(kinds, lp, x, cfg, positions=positions,
                                    cache=c, cache_len=cache_len,
                                    block_tables=block_tables,
                                    token_mask=token_mask)
                ncs.append(nc)
            return x, ncs
        x, new_stack = jax.lax.scan(body, x, (params["stack"], cache["stack"]))

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _head_logits(cfg, params, x)
    return logits, {"prefix": new_prefix, "stack": new_stack}


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            image_embeds: Optional[jax.Array] = None,
            length: Optional[jax.Array] = None,
            token_mask: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Params]:
    """Serving prefill: forward pass returning last-position logits + the
    attention KV for the processed prompt (cache at length S).

    ``length`` (B,) gives each row's true prompt length when ``tokens`` is
    right-padded to a bucket size: logits are taken at position
    ``length - 1`` instead of S-1. Pad positions produce garbage KV, which
    downstream decode masks out via per-slot ``cache_len`` — causality
    guarantees real positions never attend to right-pads. ``token_mask``
    (B, S) additionally keeps pad positions/rows out of MoE expert
    capacity (attention/MLP layers ignore it).
    """
    sp = stack_plan(cfg)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = _embed_tokens(cfg, params, tokens, image_embeds)

    new_prefix = []
    for kinds, lp in zip(sp.prefix, params["prefix"]):
        x, nc = layer_apply(kinds, lp, x, cfg, positions=positions,
                            token_mask=token_mask, want_cache=True)
        new_prefix.append(nc)

    new_stack = []
    if sp.repeats:
        def body(x, rep_params):
            ncs = []
            for kinds, lp in zip(sp.pattern, rep_params):
                x, nc = layer_apply(kinds, lp, x, cfg, positions=positions,
                                    token_mask=token_mask, want_cache=True)
                ncs.append(nc)
            return x, ncs
        if cfg.remat:
            body = jax.checkpoint(body)
        x, new_stack = jax.lax.scan(body, x, params["stack"])

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if length is None:
        last = x[:, -1:]
    else:
        idx = jnp.clip(jnp.asarray(length, jnp.int32) - 1, 0, s - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    logits = _head_logits(cfg, params, last)
    return logits, {"prefix": new_prefix, "stack": new_stack}


def prefill_append(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   cache: Params, prefix_len: jax.Array,
                   block_tables: jax.Array,
                   length: Optional[jax.Array] = None,
                   all_logits: bool = False
                   ) -> Tuple[jax.Array, Params]:
    """Suffix-only prefill over a paged cache holding a shared prefix.

    ``tokens`` (B, S) are the UNCACHED suffix tokens (right-padded to a
    bucket), ``prefix_len`` (B,) the cached positions already sitting in
    this slot's block-table pages, ``length`` (B,) the true suffix
    lengths. Each layer writes its suffix K/V into the slot's (private)
    pages at positions ``prefix_len + j`` and attends to cached prefix +
    suffix through the pages (``cfg.attn_impl`` picks the prefill-append
    kernel or the gather ref) — the shared prefix is never recomputed.
    Returns last-real-suffix-token logits (B, 1, V) + the updated cache;
    with ``prefix_len = 0`` this degenerates to an ordinary (paged)
    prefill. Attention-family layers only — recurrent mixers have no
    paged state to append to.

    ``all_logits=True`` (static) returns logits for EVERY suffix position
    (B, S, V) instead of the last real one — row ``j`` is the model's
    distribution over the token following suffix position ``j``. This is
    the speculative-decode verification read: one dispatch scores a
    drafted token block against the paged prefix, decode being the S=1
    special case.
    """
    sp = stack_plan(cfg)
    b, s = tokens.shape
    prefix_len = jnp.asarray(prefix_len, jnp.int32)
    slen = (jnp.full((b,), s, jnp.int32) if length is None
            else jnp.asarray(length, jnp.int32))
    positions = prefix_len[:, None] + jnp.arange(s)[None]
    valid = jnp.arange(s)[None] < slen[:, None]
    token_mask = valid if cfg.num_experts else None
    x = _embed_tokens(cfg, params, tokens, None)

    new_prefix = []
    for kinds, lp, c in zip(sp.prefix, params["prefix"], cache["prefix"]):
        x, nc = layer_apply(kinds, lp, x, cfg, positions=positions,
                            cache=c, cache_len=prefix_len,
                            block_tables=block_tables, suffix_len=slen,
                            token_mask=token_mask)
        new_prefix.append(nc)

    new_stack = cache["stack"]
    if sp.repeats:
        def body(x, inp):
            rep_params, rep_cache = inp
            ncs = []
            for kinds, lp, c in zip(sp.pattern, rep_params, rep_cache):
                x, nc = layer_apply(kinds, lp, x, cfg, positions=positions,
                                    cache=c, cache_len=prefix_len,
                                    block_tables=block_tables,
                                    suffix_len=slen, token_mask=token_mask)
                ncs.append(nc)
            return x, ncs
        x, new_stack = jax.lax.scan(body, x, (params["stack"], cache["stack"]))

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if all_logits:
        logits = _head_logits(cfg, params, x)
        return logits, {"prefix": new_prefix, "stack": new_stack}
    idx = jnp.clip(slen - 1, 0, s - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    logits = _head_logits(cfg, params, last)
    return logits, {"prefix": new_prefix, "stack": new_stack}
