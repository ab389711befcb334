"""Pallas TPU flash attention over a block-paged KV cache.

The masked-dense ``decode_attention`` reads the ENTIRE ``(B, capacity, Hkv,
D)`` cache every step and relies on a ``-1e30`` mask to discard dead
positions — bytes per step scale with provisioned capacity, not with what
any request has actually generated. This kernel applies GRIM's core move
(skip pruned blocks at block granularity instead of masking them) to the
KV cache: K/V live in a shared page pool ``(n_pages, Hkv, page_size, D)``
and each slot owns a block table of physical page ids, so the kernel only
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

grid = (B, Hkv / heads, ceil(n_table_cols / pages)), table steps
innermost. One grid step covers ``pages`` table columns (``T = pages *
page_size`` positions) of ``heads`` KV heads of one slot;
:func:`attention_blocks` picks both from shapes alone (below).

  1. the block table, live-page counts, prefix lengths and each slot's
     next slot with a live page arrive via scalar prefetch;
  2. one page of a head group is one contiguous ``(heads, page_size, D)``
     slab of the head-major pool. When ``D`` fills whole 128-lane tiles,
     the K and V pools stay in HBM and each live page's slab is copied
     with ``make_async_copy`` into its ``page_size`` rows of a VMEM buffer
     ``(2, heads, T, D)``. The two buffers alternate between live steps:
     a live step starts the copies of the NEXT live step (the slot's next
     table step, else its next head group, else the next live slot's
     first step) before it waits on its own, so copies overlap compute.
     Mosaic refuses a hand-made copy of a lane-padded slab (a ``D`` of 64,
     the ``(Hkv, page_size)`` int8 scale slabs), so those pools enter as
     ``pages`` pipelined blocks per step, one per page; a column past the
     slot's live pages maps to the block its operand held last, so the
     pipeline skips its copy. Dead pages are never copied, and a step with
     no live page skips copies and compute;
  3. a live step runs one online-softmax update over its ``T`` columns:
     the logits are one head-batched ``(heads, rows, D) x (heads, T, D)``
     contraction, the query block is ``S x G`` rows per head (all G
     q-heads of a kv head share the page read; decode is the S=1 special
     case), with a per-row causal mask ``pos <= prefix_len + row // G``.
     Columns of dead pages lie past every row's position, so the same
     mask drops them;
  4. the output block is revisited across the table sweep and written
     once, at the last table step.

Block sizes (:func:`attention_blocks`): ``pages`` fills one 128-lane
logits tile (``128 // page_size`` pages, at least one), capped by the
table width; ``heads`` is the largest divisor of ``Hkv`` whose working
set (two K/V buffers, int8 dequant temporaries, q, out, the online-softmax
state and the logits) fits a fixed VMEM budget — every head at decode, a
group of them for a long prefill-append suffix.

int8 pools carry sibling ``(n_pages, Hkv, page_size)`` fp32 scale pools;
a step's scale blocks are joined into ``(heads, T)`` rows and folded into
the logit and probability columns.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
_SUBLANE = 8
_LANES = 128
#: VMEM one grid step's working set may take (v5e's scoped default is 16 MiB)
_VMEM_BUDGET = 8 * 2 ** 20


def attention_blocks(page_size: int, hkv: int, d: int, itemsize: int,
                     rows: int, n_cols: int) -> Tuple[int, int]:
    """``(pages, heads)`` per grid step for a pool of ``itemsize``-byte
    elements with ``hkv`` heads of ``d``, ``rows = S * G`` query rows per
    head and a table ``n_cols`` wide: ``pages`` fills a 128-lane logits
    tile, capped by the table; ``heads`` is the largest divisor of
    ``hkv`` whose working set fits ``_VMEM_BUDGET`` (at least one)."""
    pages = max(1, min(n_cols, _LANES // page_size))
    t = pages * page_size
    rp = -(-rows // _SUBLANE) * _SUBLANE

    def vmem(h):
        kv = 2 * 2 * h * t * d * itemsize         # K, V; two buffers each
        deq = 2 * h * t * d * 4 if itemsize == 1 else 0
        q_out = 2 * 2 * h * rp * d * 4            # pipelined q and out
        state = h * rp * (d + 2 * _LANES) * 4     # acc, m, l
        logits = 3 * h * rp * t * 4               # s, prob, mask
        return kv + deq + q_out + state + logits

    heads = max((h for h in range(1, hkv + 1)
                 if hkv % h == 0 and vmem(h) <= _VMEM_BUDGET), default=1)
    return pages, heads


def attention_grid_steps(total_len, page_size: int, hkv: int, d: int,
                         itemsize: int, rows: int,
                         n_cols: int) -> Tuple[int, int]:
    """``(grid steps, live steps)`` of one kernel call whose slots attend
    over ``total_len`` positions (host ints or an array, one per table
    row): every step of the grid, and those that copy at least one live
    page."""
    pages, heads = attention_blocks(page_size, hkv, d, itemsize, rows,
                                    n_cols)
    lens = np.maximum(np.asarray(total_len, np.int64), 0)
    live_pages = -(-lens // page_size)
    groups = hkv // heads
    grid = lens.size * groups * -(-n_cols // pages)
    return int(grid), int((-(-live_pages // pages)).sum()) * groups


def _kernel(bt_ref, live_ref, plen_ref, next_ref, q_ref, *rest,
            page_size: int, pages: int, heads: int, n_cols: int,
            scale: float, group: int, quantized: bool, copied: bool):
    # operands: [K, V pools in HBM (copied)], then `pages` pipelined blocks
    # of each other pool (K, V unless copied; the int8 scale pools)
    n_hbm = 2 if copied else 0
    n_blocks = (4 if quantized else 2) - n_hbm
    hbm = rest[:n_hbm]
    blocks = [rest[n_hbm + n * pages:n_hbm + (n + 1) * pages]
              for n in range(n_blocks)]
    o_ref = rest[n_hbm + n_blocks * pages]
    if copied:
        k_buf, v_buf, sems, m_ref, l_ref, acc_ref, flags = \
            rest[n_hbm + n_blocks * pages + 1:]
    else:
        m_ref, l_ref, acc_ref = rest[n_blocks * pages + 1:]
    b, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_slots, n_groups, n_steps = (pl.num_programs(0), pl.num_programs(1),
                                  pl.num_programs(2))
    t = pages * page_size

    def page_copies(b_, g_, j_, slot):
        """The K and V copies of step (b_, g_, j_) into buffer ``slot``,
        each page's with the condition (the page is live) they run under."""
        out = []
        for i in range(pages):
            col = j_ * pages + i
            pid = bt_ref[b_, jnp.minimum(col, n_cols - 1)]
            rows_at = pl.ds(i * page_size, page_size)
            out.append((col < live_ref[b_], [
                pltpu.make_async_copy(pool.at[pid, pl.ds(g_ * heads, heads)],
                                      buf.at[slot, :, rows_at],
                                      sems.at[slot, n])
                for n, (pool, buf) in enumerate(zip(hbm, (k_buf, v_buf)))]))
        return out

    def run(copies, method):
        for cond, cps in copies:
            @pl.when(cond)
            def _():
                for cp in cps:
                    getattr(cp, method)()

    if copied:
        @pl.when((b == 0) & (g == 0) & (j == 0))
        def _first():
            flags[0] = 0      # buffer of the current live step
            flags[1] = 1      # its copies are not yet started
            if not quantized:
                # dead pages' rows are never copied: keep them finite, so
                # the zero probabilities the mask gives them zero their V
                # terms (int8 codes are always finite)
                v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = live_ref[b]

    @pl.when(j * pages < live)
    def _step():
        heads_at = pl.ds(g * heads, heads)
        if copied:
            slot = flags[0]

            @pl.when(flags[1] == 1)
            def _():
                run(page_copies(b, g, j, slot), "start")

            # the next live step in grid order: this slot's next table
            # step, else its next head group, else the next live slot's
            # first step
            slot_done = (j + 1) * pages >= live
            wrap = slot_done & (g + 1 >= n_groups)
            nb = jnp.where(wrap, next_ref[b], b)
            ng = jnp.where(slot_done, jnp.where(wrap, 0, g + 1), g)
            nj = jnp.where(slot_done, 0, j + 1)

            @pl.when(nb < n_slots)
            def _():
                run(page_copies(nb, ng, nj, 1 - slot), "start")

            flags[0] = 1 - slot
            flags[1] = (nb >= n_slots).astype(jnp.int32)
            run(page_copies(b, g, j, slot), "wait")
            k, v = k_buf[slot], v_buf[slot]       # (heads, T, D)
        else:
            k, v = (jnp.concatenate([r[0] for r in refs], axis=1)
                    for refs in blocks[:2])
        q = q_ref[0]                          # (heads, rows, D)
        if quantized:
            # int8 pages: the matmuls run on the codes cast to fp32 and
            # the per-row-per-head scales fold into the logit and
            # probability columns (one multiply per logit instead of D
            # per K element); fp32 accumulation is unchanged
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
            ks, vs = (jnp.concatenate([r[0, heads_at] for r in refs],
                                      axis=-1)     # (heads, T)
                      for refs in blocks[-2:])
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (heads, rows, T)
        if quantized:
            s = s * ks[:, None, :]
        # per-row causal mask: row r is q-head r % G of suffix position
        # r // G, at absolute position plen + r // G. For decode (S=1)
        # this degenerates to the uniform ``pos < cache_len`` mask; rows
        # padded past S*G attend garbage and are sliced off by the caller.
        pos = j * t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        qpos = plen_ref[b] + \
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // group
        s = jnp.where(pos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]                   # (heads, rows, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + prob.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        if quantized:
            prob = prob * vs[:, None, :]
        else:
            prob = prob.astype(v.dtype)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            prob, v, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_steps - 1)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _paged_attention(q, k_pages, v_pages, block_tables, prefix_len,
                     total_len, *, k_scale=None, v_scale=None,
                     interpret: bool):
    """Shared driver: q (B, S, H, D) query block per slot, row ``i`` at
    absolute position ``prefix_len[b] + i``, attending to table pages
    covering positions ``[0, total_len[b])`` under the per-row causal
    mask. Returns (B, S, H, D).

    When ``k_scale``/``v_scale`` are given the pools hold int8 codes and
    the sibling ``(n_pages, Hkv, page_size)`` scale pools carry one fp32
    scale per page row per kv head; a live page's scales travel with its
    codes and dequantization happens inside the kernel body."""
    b, s, h, d = q.shape
    n_pages, hkv, page_size, _ = k_pages.shape
    g = h // hkv
    n_cols = block_tables.shape[1]
    rows = s * g
    pages, heads = attention_blocks(page_size, hkv, d,
                                    k_pages.dtype.itemsize, rows, n_cols)
    t = pages * page_size
    # Mosaic copies a slab by hand only when its minor dim fills whole
    # 128-lane tiles (a narrower one is lane-padded in HBM), so K and V
    # pages of a lane-dense D are copied by hand, the rest pipelined
    copied = d % _LANES == 0

    # (B, Hkv, S*G, D) with the row count padded to the sublane granule so
    # the (rows, T) logits tile is legal on TPU
    qg = q.reshape(b, s, hkv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, hkv, rows, d)
    rp = -(-rows // _SUBLANE) * _SUBLANE
    if rp != rows:
        qg = jnp.concatenate(
            [qg, jnp.zeros((b, hkv, rp - rows, d), qg.dtype)], axis=2)

    plen = jnp.asarray(prefix_len, jnp.int32)
    tlen = jnp.asarray(total_len, jnp.int32)
    live = -(-tlen // page_size)          # live page count per slot
    # each slot's next slot with a live page (b when there is none), so a
    # slot's last live step can start the next one's copies
    later = jnp.where(live > 0, jnp.arange(b, dtype=jnp.int32), b)
    next_live = jax.lax.cummin(jnp.concatenate(
        [later[1:], jnp.full((1,), b, jnp.int32)]), reverse=True)

    quantized = k_scale is not None
    pools = [k_pages, v_pages] + ([k_scale, v_scale] if quantized else [])
    block = pl.BlockSpec((1, heads, rp, d),
                         lambda b_, g_, j_, *refs: (b_, g_, 0, 0))
    state = [pltpu.VMEM((heads, rp, 1), jnp.float32),    # running max m
             pltpu.VMEM((heads, rp, 1), jnp.float32),    # running denom l
             pltpu.VMEM((heads, rp, d), jnp.float32)]    # output accumulator
    # each page of a step whose pool is not copied by hand is a pipelined
    # block of its own. A column past the slot's live pages maps to the
    # block its operand held last (the same index, so the pipeline skips
    # the copy): its own column a step back, or the slot's last live page
    def col(i, b_, j_, live_ref):
        c, lv = j_ * pages + i, live_ref[b_]
        back = jnp.where(lv > i, i + pages * ((lv - 1 - i) // pages),
                         jnp.maximum(lv - 1, 0))
        return jnp.where(c < lv, c, back)

    def page_spec(i, shape):
        return pl.BlockSpec(shape, lambda b_, g_, j_, bt, lv, *_: (
            bt[b_, col(i, b_, j_, lv)], g_ if len(shape) == 4 else 0)
            + (0,) * (len(shape) - 2))

    kv_specs = [page_spec(i, (1, heads, page_size, d)) for i in range(pages)]
    sc_specs = [page_spec(i, (1, hkv, page_size)) for i in range(pages)]
    piped = pools[2:] if copied else pools
    operands = (pools[:2] if copied else []) + [
        p for p in piped for _ in range(pages)]
    in_specs = ([pl.BlockSpec(memory_space=pltpu.HBM)] * 2 if copied
                else kv_specs * 2) + sc_specs * (len(pools) - 2)
    scratch = state
    if copied:
        scratch = [pltpu.VMEM((2, heads, t, d), k_pages.dtype)] * 2 + [
            pltpu.SemaphoreType.DMA((2, 2))] + state + [
            pltpu.SMEM((2,), jnp.int32)]          # buffer, copies owed
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hkv // heads, -(-n_cols // pages)),
        in_specs=[block] + in_specs,
        out_specs=block,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _kernel, page_size=page_size, pages=pages, heads=heads,
        n_cols=n_cols, scale=d ** -0.5, group=g, quantized=quantized,
        copied=copied)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rp, d), q.dtype),
        # a live step starts the next live step's copies: sequential grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
        name="paged_attention",
    )(block_tables.astype(jnp.int32), live, plen, next_live, qg, *operands)
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
    read. With ``k_scale``/``v_scale`` the pools hold int8 codes
    dequantized in-kernel. Returns ``(B, 1, H, D)``.
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
    lens = np.maximum(np.asarray(cache_len), 0)
    pages = np.maximum(-(-lens // page_size), 1) * (lens > 0)
    row_bytes = hkv * (d * dtype_bytes + scale_bytes)
    return int(pages.sum()) * page_size * row_bytes * 2
