"""Shared neural-net substrate: norms, RoPE, attention (flash-chunked +
decode), SwiGLU MLP, embeddings, losses, and memory-safe scan helpers.

Every matmul goes through ``core.sparse_linear.linear_apply`` so BCR pruning
(dense-masked in training, TBCRC-packed at serving) is available everywhere —
the paper's CONV/FC unification generalized to "every projection is a GEMM".
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparse_linear import (grouped_linear_apply, linear_apply,
                                      linear_init)
from repro.runtime import partitioning as part
from repro.runtime.collectives import maybe_gather

Params = Dict[str, Any]


def _linear_in_dim(p: Params) -> int:
    """Input (K) dimension of a linear param dict — the full reduction
    width a tensor-parallel caller must re-replicate its activation to
    before applying it (dense and BCR-packed entries alike)."""
    if "w_packed" in p:
        return p["w_packed"].shape[1]
    return p["w"].shape[-1]


# ---------------------------------------------------------------------------
# Norms / RoPE / embeddings
# ---------------------------------------------------------------------------


def rmsnorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    angles = pos * freqs[None]
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding. x: (..., S, H, D), positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]   # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32) -> Params:
    return {"table": (jax.random.normal(key, (vocab, dim)) * dim ** -0.5).astype(dtype)}


def embed(params: Params, ids: jax.Array) -> jax.Array:
    return jnp.take(params["table"], ids, axis=0)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   *, qkv_bias: bool = False, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "wq": linear_init(ks[0], d_model, n_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wk": linear_init(ks[1], d_model, n_kv * head_dim, bias=qkv_bias, dtype=dtype),
        "wv": linear_init(ks[2], d_model, n_kv * head_dim, bias=qkv_bias, dtype=dtype),
        "wo": linear_init(ks[3], n_heads * head_dim, d_model, dtype=dtype),
    }


def _qkv(params: Params, x: jax.Array, n_heads: int, n_kv: int, head_dim: int,
         positions: jax.Array, rope_theta: float, impl: str):
    b, s, _ = x.shape
    # packed serving may fuse projections that share this activation into
    # one grouped dispatch (kernels/plan.fuse_packed_projections): all of
    # Q/K/V when GQA keeps their shapes equal, else K/V only
    if "wqkv" in params:
        q, k, v = grouped_linear_apply(params["wqkv"], x, impl=impl)
    else:
        if "wkv" in params:
            k, v = grouped_linear_apply(params["wkv"], x, impl=impl)
        else:
            k = linear_apply(params["wk"], x, impl=impl)
            v = linear_apply(params["wv"], x, impl=impl)
        q = linear_apply(params["wq"], x, impl=impl)
    q = q.reshape(b, s, n_heads, head_dim)
    k = k.reshape(b, s, n_kv, head_dim)
    v = v.reshape(b, s, n_kv, head_dim)
    if rope_theta > 0:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    q = part.act(q, "batch", "seq", "heads", "head_dim")
    k = part.act(k, "batch", "seq", "kv_heads", "head_dim")
    v = part.act(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def dense_attention(q, k, v, *, causal: bool, q_offset: int = 0) -> jax.Array:
    """Materialized-logits attention (small sequences / smoke tests)."""
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * d ** -0.5
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(skv)
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool = True, q_chunk: int = 512,
                    kv_chunk: int = 1024, q_offset: int = 0) -> jax.Array:
    """Online-softmax chunked attention (flash-style in XLA, GQA-aware).

    Never materializes more than (q_chunk × kv_chunk) logits per head; each
    q-chunk body is checkpointed so backward recomputes instead of saving
    per-kv-chunk residuals.

    Sharding (perf iteration C1, EXPERIMENTS.md §Perf): (batch, kv_heads)
    are merged into one leading dim constrained over the FULL mesh
    ("batch_heads" → pod×data×model). Head counts that don't divide the
    model axis (qwen/whisper: 20 heads on 16) would otherwise replicate all
    logits-shaped tensors across the model axis — merged, the product
    B×Hkv shards evenly and attention bytes/flops drop ~model-axis-fold.

    Static kv scan counts all chunks — causal skip of future chunks is a
    further documented perf iteration.
    """
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    assert sq % q_chunk == 0 and skv % kv_chunk == 0
    nq, nk = sq // q_chunk, skv // kv_chunk
    scale = d ** -0.5
    bh = b * hkv

    # merge (b, hkv) -> dim0. Adaptive sharding (perf iteration C1/A3):
    # when B·Hkv divides the full mesh, shard it over pod×data×model
    # (qwen/whisper: indivisible head counts); otherwise (small microbatch,
    # e.g. 405B grad accumulation) split — B·Hkv over the DP axes and the
    # GQA q-group dim over model. Without the fallback the constraint
    # silently no-ops and XLA replicates all attention work (observed 34×
    # regression on llama3-405b train).
    if part.divides(bh, "batch_heads"):
        t0, tg = "batch_heads", None
    else:
        t0, tg = "batch_kv", ("heads_g" if part.divides(g, "heads_g")
                              else None)
    qm = q.reshape(b, sq, hkv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(bh, sq, g, d)
    km = k.transpose(0, 2, 1, 3).reshape(bh, skv, d)
    vm = v.transpose(0, 2, 1, 3).reshape(bh, skv, d)
    qm = part.act(qm, t0, "seq", tg, "head_dim")
    km = part.act(km, t0, "seq", "head_dim")
    vm = part.act(vm, t0, "seq", "head_dim")

    qr = qm.reshape(bh, nq, q_chunk, g, d)
    kr = km.reshape(bh, nk, kv_chunk, d)
    vr = vm.reshape(bh, nk, kv_chunk, d)

    def kv_pair(qi, ki, qc, carry):
        """One (q-chunk, kv-chunk) tile. qi/ki are PYTHON ints (static grid,
        perf iteration C2): fully-future tiles are skipped at trace time and
        fully-past tiles skip the mask/select entirely — the causal 2×
        compute/traffic overhead of a scanned kv loop disappears."""
        m, l, acc = carry
        kc, vc = kr[:, ki], vr[:, ki]
        logits = jnp.einsum("Bqgd,Bkd->Bgqk", qc, kc,
                            preferred_element_type=jnp.float32) * scale
        logits = part.act(logits, t0, tg, None, None)
        q_lo = q_offset + qi * q_chunk
        k_lo = ki * kv_chunk
        if causal and k_lo + kv_chunk - 1 > q_lo:   # diagonal tile: mask
            qpos = q_lo + jnp.arange(q_chunk)
            kpos = k_lo + jnp.arange(kv_chunk)
            mask = qpos[:, None] >= kpos[None, :]
            logits = jnp.where(mask[None, None], logits, -1e30)
        new_m = jnp.maximum(m, logits.max(-1))
        alpha = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m[..., None])
        new_l = l * alpha + p.sum(-1)
        new_acc = acc * alpha[..., None] + jnp.einsum(
            "Bgqk,Bkd->Bgqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return new_m, new_l, new_acc

    def one_q_chunk(qi, qc):
        # qc: (bh, q_chunk, g, d)
        m = jnp.full((bh, g, q_chunk), -1e30, jnp.float32)
        l = jnp.zeros((bh, g, q_chunk), jnp.float32)
        acc = jnp.zeros((bh, g, q_chunk, d), jnp.float32)
        q_hi = q_offset + (qi + 1) * q_chunk - 1
        for ki in range(nk):
            if causal and ki * kv_chunk > q_hi:
                continue  # fully in the future: statically skipped
            m, l, acc = kv_pair(qi, ki, qc, (m, l, acc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3)  # (bh, q_chunk, g, d)

    outs = []
    for qi in range(nq):
        body = jax.checkpoint(one_q_chunk, static_argnums=(0,))
        outs.append(body(qi, qr[:, qi]))
    out = jnp.stack(outs, axis=1)  # (bh, nq, q_chunk, g, d)
    out = out.reshape(bh, sq, g, d).reshape(b, hkv, sq, g, d)
    out = out.transpose(0, 2, 1, 3, 4).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len,
                     k_scale=None, v_scale=None) -> jax.Array:
    """Single-step attention against a (possibly partially filled) cache.

    q: (B, 1, H, D); caches: (B, L, Hkv, D); cache_len: int — number of
    valid cache positions (the new token's K/V must already be written).
    Either a scalar (every row the same age) or a (B,) vector for ragged
    continuous-batching decode where each slot attends to its own history.

    Context-parallel at scale: the cache L dim stays sharded over "model"
    (kv_seq rule); the softmax/weighted-sum contractions over L partition
    into per-shard partials + small cross-shard reductions, instead of
    resharding the multi-GB cache (DESIGN.md §5).
    """
    b, _, h, d = q.shape
    l, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    if k_scale is not None:
        # int8 caches on the unpaged path: correctness fallback only — this
        # materializes a dequantized fp32 cache copy (the NB below is
        # deliberately violated); bandwidth-proportional int8 decode is
        # served by the paged Pallas kernels.
        k_cache = k_cache.astype(jnp.float32) * k_scale[..., None]
        v_cache = v_cache.astype(jnp.float32) * v_scale[..., None]
    k_cache = part.act(k_cache, "batch", "kv_seq", None, None)
    v_cache = part.act(v_cache, "batch", "kv_seq", None, None)
    qg = q.reshape(b, hkv, g, d).astype(k_cache.dtype)
    # NB: contract in the cache dtype with fp32 accumulation — an .astype on
    # the cache would materialize (and loop-hoist) an fp32 copy of the
    # entire cache (verified via dry-run HLO).
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                        preferred_element_type=jnp.float32) * d ** -0.5
    logits = part.act(logits, "batch", None, None, "kv_seq")
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        valid = jnp.arange(l) < cache_len                    # (L,)
        logits = jnp.where(valid[None, None, None], logits, -1e30)
    else:
        valid = jnp.arange(l)[None] < cache_len[:, None]     # (B, L)
        logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    p = part.act(p, "batch", None, None, "kv_seq").astype(v_cache.dtype)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def paged_decode_attention_dispatch(q, k_pages, v_pages, block_tables,
                                    cache_len, attn_impl: str,
                                    k_scale=None, v_scale=None) -> jax.Array:
    """Paged single-step attention: the Pallas flash-decode kernel when
    ``attn_impl`` asks for it ("paged" compiled, "paged_interpret" for CPU
    validation), else the pure-JAX gather ref — whose bytes still scale
    with the table width handed in, not the slot capacity. With
    ``k_scale``/``v_scale`` the pools hold int8 codes dequantized inside
    the kernel (or after the ref's gather)."""
    from repro.kernels.paged_decode_attention import paged_decode_attention
    from repro.kernels.ref import paged_decode_attention_ref
    if attn_impl in ("paged", "paged_interpret"):
        return paged_decode_attention(
            q, k_pages, v_pages, block_tables, cache_len,
            k_scale=k_scale, v_scale=v_scale,
            interpret=(attn_impl == "paged_interpret"))
    return paged_decode_attention_ref(q, k_pages, v_pages, block_tables,
                                      cache_len, k_scale=k_scale,
                                      v_scale=v_scale)


def paged_prefill_append_dispatch(q, k_pages, v_pages, block_tables,
                                  prefix_len, total_len, attn_impl: str,
                                  k_scale=None, v_scale=None) -> jax.Array:
    """Prefill-append attention: the multi-query generalization of the
    flash-decode kernel (suffix rows run online softmax over the slot's
    cached prefix pages + a causal mask inside the chunk) or the pure-JAX
    gather ref, chosen exactly like the decode dispatch."""
    from repro.kernels.paged_decode_attention import (
        paged_prefill_append_attention)
    from repro.kernels.ref import paged_prefill_append_ref
    if attn_impl in ("paged", "paged_interpret"):
        return paged_prefill_append_attention(
            q, k_pages, v_pages, block_tables, prefix_len, total_len,
            k_scale=k_scale, v_scale=v_scale,
            interpret=(attn_impl == "paged_interpret"))
    return paged_prefill_append_ref(q, k_pages, v_pages, block_tables,
                                    prefix_len, total_len, k_scale=k_scale,
                                    v_scale=v_scale)


def _paged_write(pages, scale_pool, dest, rows, n_kv, head_dim):
    """Scatter K/V rows into a head-major page pool ``(n_pages, Hkv,
    page_size, D)`` at flat row positions ``dest = page * page_size +
    offset``.

    Unquantized pools store ``rows`` cast to the pool dtype. int8 pools
    (``scale_pool`` not None) quantize on store: each row gets a per-kv-
    head symmetric scale written into the sibling ``(n_pages, Hkv,
    page_size)`` scale pool at the same position, so the pool and its
    scales can never drift apart (CoW copies, truncation and eviction all
    move them together). Returns ``(pages, scale_pool)``.
    """
    page_size = pages.shape[2]
    pid, off = dest // page_size, dest % page_size
    rows = rows.reshape(-1, n_kv, head_dim)
    if scale_pool is None:
        return pages.at[pid, :, off].set(rows.astype(pages.dtype)), None
    from repro.kernels.quant import quantize_rows
    codes, scales = quantize_rows(rows)
    pages = pages.at[pid, :, off].set(codes)
    scale_pool = scale_pool.at[pid, :, off].set(
        scales.astype(scale_pool.dtype))
    return pages, scale_pool


def attention_apply(
    params: Params, x: jax.Array, *, n_heads: int, n_kv: int, head_dim: int,
    positions: jax.Array, rope_theta: float = 10000.0, causal: bool = True,
    cache: Optional[Params] = None, cache_len: Optional[jax.Array] = None,
    block_tables: Optional[jax.Array] = None,
    suffix_len: Optional[jax.Array] = None,
    attn_impl: str = "flash", q_chunk: int = 512, kv_chunk: int = 1024,
    impl: str = "ref", tp_axis: str = "",
) -> Tuple[jax.Array, Optional[Params]]:
    """Full attention block. With ``cache`` → single-token decode step.

    ``tp_axis`` names the tensor-parallel mesh axis when the block runs
    inside the sharded engine's shard_map (``repro.serving.tp``):
    ``n_heads``/``n_kv`` are then the LOCAL per-shard head counts, the
    cache leaves are the local ``Hkv`` slice, and the block re-replicates
    via all-gather (never a reduce — summation order must stay bit-equal
    to single-device) at exactly two points: the head axis before ``wo``
    (whose reduction spans all heads) and the ``wo`` output (the residual
    stream stays replicated).

    With ``block_tables`` the cache leaves are a shared page pool
    ``(n_pages, Hkv, page_size, D)`` instead of per-slot capacity rows:
    the step's K/V scatter into each slot's current page and attention
    reads only table pages (see kernels/paged_decode_attention.py).
    ``s > 1`` with a paged cache is the prefill-append path: ``cache_len``
    then counts the cached prefix positions, ``suffix_len`` the true
    (pre-padding) suffix rows, and the block writes its S suffix K/V rows
    at positions ``cache_len + i`` before attending to prefix + suffix
    through the pages.
    """
    b, s, _ = x.shape
    with jax.named_scope("projections"):
        q, k, v = _qkv(params, x, n_heads, n_kv, head_dim, positions,
                       rope_theta, impl)

    if cache is not None and block_tables is not None and s > 1:
        # prefill-append: scatter the suffix K/V rows into the slot's own
        # (private) pages — positions prefix_len + j, with pad rows/
        # positions routed to the null page — then attend to cached prefix
        # pages + the just-written suffix pages. Shared prefix pages are
        # never recomputed OR rewritten (admission CoW guarantees the
        # suffix's first page is private before this runs).
        plen = jnp.asarray(cache_len)
        slen = jnp.asarray(suffix_len)
        ck, cv = cache["k"], cache["v"]
        ks_pool, vs_pool = cache.get("k_scale"), cache.get("v_scale")
        page_size = ck.shape[2]
        n_cols = block_tables.shape[1]
        pos = plen[:, None] + jnp.arange(s)[None]            # (B, S)
        valid = jnp.arange(s)[None] < slen[:, None]
        col = jnp.clip(pos // page_size, 0, n_cols - 1)
        dest = (jnp.take_along_axis(block_tables, col, axis=1) * page_size
                + pos % page_size)
        dest = jnp.where(valid, dest, 0).reshape(-1)
        k_pages, ks_pool = _paged_write(ck, ks_pool, dest, k, n_kv, head_dim)
        v_pages, vs_pool = _paged_write(cv, vs_pool, dest, v, n_kv, head_dim)
        out = paged_prefill_append_dispatch(
            q, k_pages, v_pages, block_tables, plen, plen + slen, attn_impl,
            k_scale=ks_pool, v_scale=vs_pool)
        new_cache = {"k": k_pages, "v": v_pages}
        if ks_pool is not None:
            new_cache.update(k_scale=ks_pool, v_scale=vs_pool)
    elif cache is not None and block_tables is not None:
        # paged decode: write K/V at flat position table[b, len // ps] * ps
        # + len % ps. Inactive slots (len 0, zeroed table row) land in the
        # reserved null page 0, which no live table entry ever points at.
        idx = jnp.asarray(cache_len)
        ck, cv = cache["k"], cache["v"]
        ks_pool, vs_pool = cache.get("k_scale"), cache.get("v_scale")
        page_size = ck.shape[2]
        with jax.named_scope("kv_write"):
            dest = (jnp.take_along_axis(
                block_tables, (idx // page_size)[:, None], axis=1)[:, 0]
                * page_size + idx % page_size)
            k_pages, ks_pool = _paged_write(ck, ks_pool, dest, k[:, 0],
                                            n_kv, head_dim)
            v_pages, vs_pool = _paged_write(cv, vs_pool, dest, v[:, 0],
                                            n_kv, head_dim)
        with jax.named_scope("attention"):
            out = paged_decode_attention_dispatch(
                q, k_pages, v_pages, block_tables, idx + 1, attn_impl,
                k_scale=ks_pool, v_scale=vs_pool)
        new_cache = {"k": k_pages, "v": v_pages}
        if ks_pool is not None:
            new_cache.update(k_scale=ks_pool, v_scale=vs_pool)
    elif cache is not None:
        # decode: write K/V at position cache_len, attend to ≤ cache_len+1.
        # cache_len is a scalar (uniform batch) or a (B,) vector (ragged
        # continuous batch: each slot writes at and attends to its own
        # length).
        idx = jnp.asarray(cache_len)
        ck = part.act(cache["k"], "batch", "kv_seq", None, None)
        cv = part.act(cache["v"], "batch", "kv_seq", None, None)
        ks_cache, vs_cache = cache.get("k_scale"), cache.get("v_scale")
        ku, vu = k.astype(ck.dtype), v.astype(cv.dtype)
        ksu = vsu = None
        if ks_cache is not None:
            from repro.kernels.quant import quantize_rows
            ku, ksu = quantize_rows(k)
            vu, vsu = quantize_rows(v)
        if idx.ndim == 0:
            def write(c, u):
                return jax.lax.dynamic_update_slice_in_dim(c, u, idx, axis=1)
        else:
            def write(c, u):
                return jax.vmap(
                    lambda cc, uu, i: jax.lax.dynamic_update_slice_in_dim(
                        cc, uu, i, axis=0))(c, u, idx)
        k_cache, v_cache = write(ck, ku), write(cv, vu)
        new_cache = {"k": k_cache, "v": v_cache}
        if ks_cache is not None:
            ks_cache, vs_cache = write(ks_cache, ksu), write(vs_cache, vsu)
            new_cache.update(k_scale=ks_cache, v_scale=vs_cache)
        out = decode_attention(q, k_cache, v_cache, idx + s,
                               k_scale=ks_cache, v_scale=vs_cache)
    else:
        if attn_impl == "dense":
            out = dense_attention(q, k, v, causal=causal)
        elif attn_impl in ("pallas", "pallas_interpret"):
            # fused Pallas kernel on the merged-head layout (TPU target;
            # interpret mode for CPU validation). GQA: K/V broadcast to all
            # q heads (documented trade: duplicates KV reads in exchange
            # for the fused online-softmax VMEM residency).
            from repro.kernels.flash_attention import flash_attention_fused
            bq, sq, hq, dh = q.shape
            g = hq // k.shape[2]
            km = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3) \
                .reshape(bq * hq, k.shape[1], dh)
            vm = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3) \
                .reshape(bq * hq, v.shape[1], dh)
            qm = q.transpose(0, 2, 1, 3).reshape(bq * hq, sq, dh)
            out = flash_attention_fused(
                qm, km, vm, causal=causal, q_chunk=q_chunk,
                kv_chunk=kv_chunk,
                interpret=(attn_impl == "pallas_interpret"))
            out = out.reshape(bq, hq, sq, dh).transpose(0, 2, 1, 3)
        else:
            out = flash_attention(q, k, v, causal=causal,
                                  q_chunk=q_chunk, kv_chunk=kv_chunk)
        new_cache = {"k": k, "v": v}
    out = part.act(out, "batch", "seq", "heads", "head_dim")
    if tp_axis:
        out = maybe_gather(out, _linear_in_dim(params["wo"]) // head_dim,
                           tp_axis, axis=2)
    with jax.named_scope("projections"):
        y = linear_apply(params["wo"],
                         out.reshape(b, s, out.shape[2] * head_dim),
                         impl=impl)
    y = maybe_gather(y, x.shape[-1], tp_axis)
    return y, new_cache


def cross_attention_apply(
    params: Params, x: jax.Array, kv_cache: Params, *, n_heads: int,
    n_kv: int, head_dim: int, impl: str = "ref",
) -> jax.Array:
    """Encoder-decoder cross attention against precomputed encoder K/V.

    Q always stays un-fused here (packing never groups it into a "wqkv"
    for cross-attention — Q projects the decoder stream while K/V project
    encoder output, see plan.fuse_packed_projections)."""
    b, s, _ = x.shape
    q = linear_apply(params["wq"], x, impl=impl).reshape(b, s, n_heads, head_dim)
    k, v = kv_cache["k"], kv_cache["v"]
    if s == 1:
        out = decode_attention(q, k, v, jnp.asarray(k.shape[1]))
    else:
        out = flash_attention(q, k, v, causal=False)
    y = linear_apply(params["wo"], out.reshape(b, s, n_heads * head_dim), impl=impl)
    return y


def cross_kv(params: Params, enc_out: jax.Array, *, n_kv: int,
             head_dim: int, impl: str = "ref") -> Params:
    b, s, _ = enc_out.shape
    if "wkv" in params:
        k, v = grouped_linear_apply(params["wkv"], enc_out, impl=impl)
    else:
        k = linear_apply(params["wk"], enc_out, impl=impl)
        v = linear_apply(params["wv"], enc_out, impl=impl)
    return {"k": k.reshape(b, s, n_kv, head_dim),
            "v": v.reshape(b, s, n_kv, head_dim)}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_init(key, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "wg": linear_init(ks[0], d_model, d_ff, dtype=dtype),
        "wi": linear_init(ks[1], d_model, d_ff, dtype=dtype),
        "wo": linear_init(ks[2], d_ff, d_model, dtype=dtype),
    }


def swiglu_apply(params: Params, x: jax.Array, impl: str = "ref",
                 tp_axis: str = "") -> jax.Array:
    if "wgi" in params:
        # packed serving: ONE fused gate/up dispatch whose epilogue applies
        # bias + silu(g)·h in the matmul's emit step — no separate
        # elementwise pass over the (B, S, d_ff) hidden
        h = grouped_linear_apply(params["wgi"], x, impl=impl,
                                 epilogue="swiglu")
    else:
        g = linear_apply(params["wg"], x, impl=impl)
        hu = linear_apply(params["wi"], x, impl=impl)
        h = jax.nn.silu(g) * hu
    h = part.act(h, "batch", "seq", "mlp")
    if tp_axis:
        # column-parallel gate/up made a LOCAL d_ff slice (silu·mul is
        # elementwise, so it commutes with the shard); re-replicate to
        # wo's full reduction width — gather, not reduce-scatter, keeps
        # the fp32 summation order bit-equal to single-device
        h = maybe_gather(h, _linear_in_dim(params["wo"]), tp_axis)
    y = linear_apply(params["wo"], h, impl=impl)
    return maybe_gather(y, x.shape[-1], tp_axis)


def gelu_mlp_init(key, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "wi": linear_init(ks[0], d_model, d_ff, bias=True, dtype=dtype),
        "wo": linear_init(ks[1], d_ff, d_model, bias=True, dtype=dtype),
    }


def gelu_mlp_apply(params: Params, x: jax.Array, impl: str = "ref",
                   tp_axis: str = "") -> jax.Array:
    h = jax.nn.gelu(linear_apply(params["wi"], x, impl=impl))
    h = part.act(h, "batch", "seq", "mlp")
    if tp_axis:
        h = maybe_gather(h, _linear_in_dim(params["wo"]), tp_axis)
    y = linear_apply(params["wo"], h, impl=impl)
    return maybe_gather(y, x.shape[-1], tp_axis)


# ---------------------------------------------------------------------------
# Losses / scan helpers
# ---------------------------------------------------------------------------


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Token-mean CE, fp32-stable; logits (..., V), targets (...)."""
    logits = part.act(logits.astype(jnp.float32), "batch", "seq", "vocab")
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def chunked_checkpoint_scan(body, carry, xs, chunk: int):
    """scan(body) over time in checkpointed chunks: O(T/chunk) live carries.

    Memory for backward = carries at chunk boundaries + recompute within a
    chunk. Used by SSM/RWKV recurrences (DESIGN.md §5).
    """
    leaves = jax.tree_util.tree_leaves(xs)
    t = leaves[0].shape[0]
    assert t % chunk == 0, (t, chunk)
    n = t // chunk
    xs_r = jax.tree_util.tree_map(
        lambda a: a.reshape((n, chunk) + a.shape[1:]), xs)

    def inner(c, xc):
        return jax.lax.scan(body, c, xc)

    inner_ckpt = jax.checkpoint(inner)
    carry, ys = jax.lax.scan(inner_ckpt, carry, xs_r)
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape((t,) + a.shape[2:]), ys)
    return carry, ys
