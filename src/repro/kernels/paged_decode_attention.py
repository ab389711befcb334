"""Pallas TPU flash attention over a block-paged KV cache.

The masked-dense ``decode_attention`` reads the ENTIRE ``(B, capacity, Hkv,
D)`` cache every step and relies on a ``-1e30`` mask to discard dead
positions — bytes per step scale with provisioned capacity, not with what
any request has actually generated. This kernel applies GRIM's core move
(skip pruned blocks at block granularity instead of masking them) to the
KV cache: K/V live in a shared page pool ``(n_pages, Hkv, page_size, D)``
and each slot owns a block table of physical page ids, so the grid only
*reads* each slot's live pages.

ONE kernel body serves two entry points:

* :func:`paged_decode_attention` — the decode hot loop: 1 query row per
  slot (``S = 1``), each at position ``cache_len - 1``.
* :func:`paged_prefill_append_attention` — suffix prefill over a shared
  prefix: an ``S``-row query block per slot whose row ``i`` sits at
  absolute position ``prefix_len + i`` and attends to every cached page
  position ``<= prefix_len + i`` (online softmax over the prefix pages,
  causal mask inside the chunk). The suffix K/V must already be scattered
  into the slot's pages before the call — the kernel reads *pages only*.

grid = (B, Hkv, n_table_cols), pages innermost. Per (slot b, kv-head h):

  1. the block table, live-page counts and per-slot prefix lengths arrive
     via scalar prefetch, so the K/V BlockSpec index maps can translate
     the logical page ``p`` of slot ``b`` into a physical page id *before*
     the body runs;
  2. dead steps (``p`` at/past the slot's live page count) clamp the index
     map to the last live page — Pallas elides the DMA when consecutive
     grid steps map to the same block, so a slot's HBM traffic is its live
     pages, not the table width — and skip all compute via ``pl.when``;
  3. live steps run one online-softmax accumulation over the page: the
     query block is ``S x G`` rows (all G q-heads of kv-head h share the
     page read; decode is the S=1 special case), with a per-row causal
     mask ``pos <= prefix_len + row // G``. Interior prefix pages are
     fully live for every row; only the final partial page and the
     suffix's own pages pay a partially-masked tile;
  4. the output block is revisited across the page sweep and written once,
     at the last grid step.

VMEM residency per (b, h): q (S*G, D), one K page + one V page, and the
(S*G, 1)/(S*G, D) online-softmax state — independent of context length
(but linear in the suffix chunk S, which the engine buckets).

The pool is head-major so that one (page, kv-head) block has the full
``(page_size, D)`` as its two minor dims — the TPU lowering requires a
block's minor dims to be (8, 128)-aligned or full. int8 scale pools are
``(n_pages, Hkv, page_size)``; their block is the whole ``(Hkv,
page_size)`` page slab (again: full minor dims) and the body picks row h.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
_SUBLANE = 8


def _kernel(bt_ref, live_ref, plen_ref, q_ref, k_ref, v_ref, *rest,
            page_size: int, n_cols: int, scale: float, group: int,
            quantized: bool):
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        o_ref, m_ref, l_ref, acc_ref = rest[2:]
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    p = pl.program_id(2)                  # logical page of this slot
    b = pl.program_id(0)
    h = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    plen = plen_ref[b]

    @pl.when(p < live_ref[b])
    def _page():
        q = q_ref[0, 0]                   # (S*G padded, D)
        k = k_ref[0, 0]                   # (page_size, D)
        v = v_ref[0, 0]
        if quantized:
            # int8 pages: the matmul runs on the raw codes and the
            # per-row-per-head scale is folded into the logits columns
            # (one multiply per logit instead of D per K element); fp32
            # accumulation is unchanged.
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (rows, page_size)
        if quantized:
            s = s * ks_ref[0, pl.ds(h, 1), :]      # (1, page_size)
        # per-row causal mask: row r is q-head r % G of suffix position
        # r // G, at absolute position plen + r // G. For decode (S=1)
        # this degenerates to the uniform ``pos < cache_len`` mask; rows
        # padded past S*G attend garbage and are sliced off by the caller.
        pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = plen + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        s = jnp.where(pos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]               # (rows, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + prob.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        if quantized:
            # fold the V scale into the probability columns, then run the
            # weighted sum on the raw int8 codes in fp32
            pv = prob * vs_ref[0, pl.ds(h, 1), :]
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                pv, v.astype(jnp.float32),
                preferred_element_type=jnp.float32)
        else:
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                prob.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(p == n_cols - 1)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _paged_attention(q, k_pages, v_pages, block_tables, prefix_len,
                     total_len, *, k_scale=None, v_scale=None,
                     interpret: bool):
    """Shared driver: q (B, S, H, D) query block per slot, row ``i`` at
    absolute position ``prefix_len[b] + i``, attending to table pages
    covering positions ``[0, total_len[b])`` under the per-row causal
    mask. Returns (B, S, H, D).

    When ``k_scale``/``v_scale`` are given the pools hold int8 codes and
    the sibling ``(n_pages, Hkv, page_size)`` scale pools carry one fp32
    scale per page row per kv head; scale tiles ride the same clamped
    index map as their pages (so dead steps elide the scale DMA too) and
    dequantization happens inside the kernel body."""
    b, s, h, d = q.shape
    n_pages, hkv, page_size, _ = k_pages.shape
    g = h // hkv
    n_cols = block_tables.shape[1]
    scale = d ** -0.5

    # (B, Hkv, S*G, D) with the row count padded to the sublane granule so
    # the (rows, page_size) logits tile is legal on TPU
    qg = q.reshape(b, s, hkv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, hkv, s * g, d)
    rows = s * g
    rp = -(-rows // _SUBLANE) * _SUBLANE
    if rp != rows:
        qg = jnp.concatenate(
            [qg, jnp.zeros((b, hkv, rp - rows, d), qg.dtype)], axis=2)

    plen = jnp.asarray(prefix_len, jnp.int32)
    tlen = jnp.asarray(total_len, jnp.int32)
    live = -(-tlen // page_size)          # live page count per slot

    def k_map(b_, h_, p_, bt_ref, live_ref, plen_ref):
        # dead steps re-reference the slot's last live page (floored at
        # table column 0 for fully dead slots): the block index is
        # unchanged from the previous step, so Pallas skips the DMA —
        # per-slot HBM traffic is live pages, not table width
        col = jnp.minimum(p_, jnp.maximum(live_ref[b_] - 1, 0))
        return bt_ref[b_, col], h_, 0, 0

    def s_map(b_, h_, p_, bt_ref, live_ref, plen_ref):
        col = jnp.minimum(p_, jnp.maximum(live_ref[b_] - 1, 0))
        return bt_ref[b_, col], 0, 0

    quantized = k_scale is not None
    in_specs = [
        pl.BlockSpec((1, 1, rp, d),
                     lambda b_, h_, p_, *refs: (b_, h_, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d), k_map),
        pl.BlockSpec((1, 1, page_size, d), k_map),
    ]
    operands = [qg, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((1, hkv, page_size), s_map),
                     pl.BlockSpec((1, hkv, page_size), s_map)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, n_cols),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rp, d),
                               lambda b_, h_, p_, *refs: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rp, 1), jnp.float32),    # running max m
            pltpu.VMEM((rp, 1), jnp.float32),    # running denom l
            pltpu.VMEM((rp, d), jnp.float32),    # output accumulator
        ],
    )
    kernel = functools.partial(
        _kernel, page_size=page_size, n_cols=n_cols, scale=scale, group=g,
        quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rp, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables.astype(jnp.int32), live, plen, *operands)
    out = out[:, :, :rows, :].reshape(b, hkv, s, g, d)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, s, h, d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jax.Array,              # (B, 1, H, D)
    k_pages: jax.Array,        # (n_pages, Hkv, page_size, D)
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, n_cols) int32 physical page ids
    cache_len: jax.Array,      # (B,) valid positions incl. the new token
    *,
    k_scale: jax.Array | None = None,  # (n_pages, Hkv, page_size) fp32
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-step attention against each slot's live pages only.

    ``block_tables`` may be narrower than the slot's full capacity — the
    caller hands over only as many columns as the longest live slot needs
    (bucketed by the engine); entries past a slot's live pages are never
    read (index-map clamp + ``pl.when``). With ``k_scale``/``v_scale``
    the pools hold int8 codes dequantized in-kernel. Returns
    ``(B, 1, H, D)``.
    """
    assert q.shape[1] == 1, "paged_decode_attention is a single-step kernel"
    lens = jnp.asarray(cache_len, jnp.int32)
    return _paged_attention(q, k_pages, v_pages, block_tables,
                            lens - 1, lens, k_scale=k_scale,
                            v_scale=v_scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_append_attention(
    q: jax.Array,              # (B, S, H, D) — S suffix rows per slot
    k_pages: jax.Array,        # (n_pages, Hkv, page_size, D)
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, n_cols) int32 physical page ids
    prefix_len: jax.Array,     # (B,) cached positions BEFORE the suffix
    total_len: jax.Array,      # (B,) prefix_len + true suffix length
    *,
    k_scale: jax.Array | None = None,  # (n_pages, Hkv, page_size) fp32
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Prefill-append: the uncached suffix attends to cached prefix pages
    without re-running them (multi-query generalization of the decode
    kernel — decode is the S=1, prefix_len=cache_len-1 special case).

    The suffix K/V rows must already be scattered into the slot's table
    pages (positions ``prefix_len + i``); the kernel reads pages only.
    Rows at/past a slot's true suffix length produce garbage output that
    the caller discards (per-row logits are taken at the true last token).
    Returns ``(B, S, H, D)``.
    """
    return _paged_attention(q, k_pages, v_pages, block_tables,
                            prefix_len, total_len, k_scale=k_scale,
                            v_scale=v_scale, interpret=interpret)


def paged_kv_bytes(cache_len, page_size: int, hkv: int, d: int,
                   dtype_bytes: int = 2, scale_bytes: int = 0) -> int:
    """HBM bytes this kernel reads per layer per step: each slot's live
    pages, K + V (the masked-dense path reads B × capacity instead).

    ``dtype_bytes`` is the POOL element's itemsize — pass the actual
    leaf dtype's size (1 under int8, 2 under bf16, 4 under fp32), not an
    assumed activation width. ``scale_bytes`` is the per-row-per-head
    sibling scale pool's itemsize (4 for the fp32 scales the int8 path
    stores, 0 when unquantized) — the kernel streams one scale per page
    row per kv head alongside each K and each V page.

    ``cache_len`` follows the kernel's contract — valid positions
    INCLUDING the step's new token (the engine's ``kv_bytes_read_live``
    stat is the same sum over all attention layers, fed ``lens + 1``
    since pool lengths exclude the token being decoded)."""
    import numpy as np
    lens = np.maximum(np.asarray(cache_len), 0)
    pages = np.maximum(-(-lens // page_size), 1) * (lens > 0)
    row_bytes = hkv * (d * dtype_bytes + scale_bytes)
    return int(pages.sum()) * page_size * row_bytes * 2
