"""Pallas TPU kernel: BCR block-sparse matmul over TBCRC-packed weights.

TPU-native redesign of GRIM's sparse codegen (DESIGN.md §2). The kernel
computes ``y[M, N] = x[M, K] @ W.T`` where ``W (N, K)`` is balanced-BCR
pruned and stored packed: per block a dense ``(R_keep, C_keep)`` value tile
plus int32 index planes. Only surviving weight bytes are ever DMA'd from
HBM — on the bandwidth-bound decode step that converts the pruning rate
directly into step-time (the mobile-latency analogue, DESIGN.md §2).

Mechanics per grid step ``(i = output block-row, j = contraction block)``:

  1. ``x`` block ``(M_t, bc)`` and the packed tile are DMA'd to VMEM by the
     BlockSpec machinery (double-buffered by Pallas).
  2. gather   : one-hot ``(bc, C_keep)`` matmul on the MXU — selects the
     surviving columns.
  3. core     : ``(M_t, C_keep) x (C_keep, R_keep)`` dense tile matmul.
  4. scatter  : one-hot ``(R_keep, br)`` matmul back to block-row layout,
     accumulated in an fp32 VMEM scratch across ``j`` (revisiting pattern —
     the output block is written once, at the last contraction step).

The pack-time execution plan (kernels/plan.py) steers dispatch:

* ``plan.use_planes`` — the gather/scatter one-hots are precomputed int8
  planes DMA'd with the tile instead of rebuilt from the index planes on
  the VPU every grid step (the §4.5 tuner trades plane bytes vs VPU time
  per layer shape).
* ``plan.grid_order`` — 'mij' (m outermost) or 'imj' (block-row outermost);
  the contraction dim stays innermost in both (accumulator correctness).
* ``plan.m_tile`` — tuned rows of ``x`` per grid step.

``bcr_spmm_grouped`` fuses G same-shaped packed weights that share the same
activation (Q/K/V, gate/up): one ``pallas_call``, the ``x`` block is DMA'd
once per (i, j) step for the whole group, the per-grid-step launch cost and
the ``m·k·2·nb_r`` HBM x re-reads are amortized G-fold. Its emit step fuses
the per-member bias add and (for gate/up) the SwiGLU activation straight
off the fp32 VMEM accumulator, so grouped projections pay no separate
elementwise dispatch after the matmul.

Register-level LRE (§4.4) maps to: the accumulator and the ``x`` block stay
resident in VMEM across grid steps that share them; the gather one-hot is
built from indices already in VMEM (no HBM index traffic per row — the
TBCRC index planes are the whole per-block metadata, mirroring BCRC's
column-index dedup).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.bcrc import TBCRC

_ORDERS = ("mij", "imj")


def _block_update(x, vals, gather, scatter, scale=None, *,
                  scatter_t: bool = False):
    """gather → core tile matmul → scatter; returns the fp32 (M_t, br)
    contribution of one (i, j) block.

    ``scatter`` is the ``(R_keep, br)`` one-hot, or with ``scatter_t`` its
    transpose ``(br, R_keep)`` (contracted on its minor dim).

    ``scale``: per-block dequant scalar for int8 ``vals`` — folded into
    the fp32 partial BEFORE the scatter (exact, the scatter one-hot is
    0/1), so the epilogue costs one multiply per partial element. int8
    codes (≤127) cast to the activation dtype losslessly (bf16 holds
    integers to 256)."""
    xg = jnp.dot(x, gather, preferred_element_type=jnp.float32)
    part = jax.lax.dot_general(
        xg.astype(x.dtype), vals.astype(x.dtype),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if scale is not None:
        part = part * scale
    return jax.lax.dot_general(
        part, scatter, dimension_numbers=(((1,), (int(scatter_t),)), ((), ())),
        preferred_element_type=jnp.float32)


def _onehots(cols, rows, block_rows, block_cols, dtype):
    """Index rows ``(1, C_keep)`` / ``(1, R_keep)`` → one-hot gather
    ``(bc, C_keep)`` and TRANSPOSED scatter ``(br, R_keep)`` (VPU iota +
    compare; both broadcast the index row down the sublanes, so no
    lane→sublane transpose is needed)."""
    iota_c = jax.lax.broadcasted_iota(jnp.int32,
                                      (block_cols, cols.shape[-1]), 0)
    gather = (iota_c == cols).astype(dtype)
    iota_r = jax.lax.broadcasted_iota(jnp.int32,
                                      (block_rows, rows.shape[-1]), 0)
    scatter_t = (iota_r == rows).astype(jnp.float32)
    return gather, scatter_t


def _block_scale(row, j):
    """Per-block dequant scale ``scales[i, j]`` as a ``(1, 1)`` value.

    The whole ``(nb_r, nb_c)`` scale matrix is one VMEM block (its minor
    dims are then full, as the TPU lowering requires of a block); the
    caller loads row ``i`` as a dynamic sublane slice ``(1, nb_c)`` and
    lane ``j`` is picked here by a masked lane reduction."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == j, row, 0.0), axis=1, keepdims=True)


def _kernel_idx(x_ref, vals_ref, row_ref, col_ref, *rest,
                nb_c: int, block_rows: int, block_cols: int,
                has_scale: bool, row_axis: int):
    scale_ref = rest[0] if has_scale else None
    o_ref, acc_ref = rest[-2], rest[-1]
    j = pl.program_id(2)  # contraction dim is innermost in both orders

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                      # (M_t, bc)
    # index planes arrive as the block row's whole (nb_c, keep) slab
    gather, scatter_t = _onehots(col_ref[0, pl.ds(j, 1), :],
                                 row_ref[0, pl.ds(j, 1), :],
                                 block_rows, block_cols, x.dtype)
    scale = (_block_scale(scale_ref[pl.ds(pl.program_id(row_axis), 1), :], j)
             if has_scale else None)
    acc_ref[...] += _block_update(x, vals_ref[0, 0], gather, scatter_t,
                                  scale, scatter_t=True)

    @pl.when(j == nb_c - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_planes(x_ref, vals_ref, gpl_ref, spl_ref, *rest,
                   nb_c: int, has_scale: bool, row_axis: int):
    scale_ref = rest[0] if has_scale else None
    o_ref, acc_ref = rest[-2], rest[-1]
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    gather = gpl_ref[0, 0].astype(x.dtype)          # (bc, C_keep) int8 DMA
    scatter = spl_ref[0, 0].astype(jnp.float32)     # (R_keep, br)
    scale = (_block_scale(scale_ref[pl.ds(pl.program_id(row_axis), 1), :], j)
             if has_scale else None)
    acc_ref[...] += _block_update(x, vals_ref[0, 0], gather, scatter, scale)

    @pl.when(j == nb_c - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _grid_and_maps(order: str, m_steps: int, nb_r: int, nb_c: int):
    """Grid tuple + (x, tile, out) index-map factories for a legal order.

    Index maps receive grid args positionally; we normalize to (s, i, j).
    """
    if order == "mij":
        grid = (m_steps, nb_r, nb_c)
        def norm(s, i, j):
            return s, i, j
    elif order == "imj":
        grid = (nb_r, m_steps, nb_c)
        def norm(i, s, j):
            return s, i, j
    else:
        raise ValueError(f"grid_order {order!r} not in {_ORDERS}")
    x_map = lambda *g: (norm(*g)[0], norm(*g)[2])
    out_map = lambda *g: (norm(*g)[0], norm(*g)[1])
    return grid, norm, x_map, out_map


def _row_axis(order: str) -> int:
    """Grid axis that walks the output block rows ``i``."""
    return 1 if order == "mij" else 0


@functools.partial(jax.jit, static_argnames=("m_tile", "interpret"))
def bcr_spmm(
    x: jax.Array,
    packed: TBCRC,
    *,
    m_tile: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """``y[M, N] = x[M, K] @ W.T`` for balanced-BCR packed ``W``.

    ``m_tile``: rows of ``x`` per grid step; defaults to the plan's tuned
    tile when one exists, else all of M (decode batches fit VMEM
    comfortably; prefill callers tile).
    """
    m, k = x.shape
    n = packed.shape[0]
    br, bc = packed.block_shape
    nb_r, nb_c, r_keep, c_keep = packed.vals.shape
    if packed.shape[1] != k:
        raise ValueError(f"x K dim {k} != packed K dim {packed.shape[1]}")

    plan = packed.plan
    if m_tile is None and plan is not None and plan.m_tile:
        m_tile = plan.m_tile if m % plan.m_tile == 0 else None
    m_tile = m_tile or m
    if m % m_tile:
        raise ValueError(f"M={m} not divisible by m_tile={m_tile}")
    m_steps = m // m_tile
    order = plan.grid_order if plan is not None else "mij"
    use_planes = plan is not None and plan.use_planes

    has_scale = plan is not None and plan.block_scales is not None

    grid, norm, x_map, out_map = _grid_and_maps(order, m_steps, nb_r, nb_c)
    tile_i = lambda *g: (norm(*g)[1], norm(*g)[2], 0, 0)
    # Every block below keeps its two minor dims full or (8, 128)-aligned,
    # as the TPU lowering requires: index planes come in as the block
    # row's whole (nb_c, keep) slab and scales as the whole matrix; the
    # kernel picks column j (and row i) itself.
    idx_i = lambda *g: (norm(*g)[1], 0, 0)

    if use_planes:
        kernel = functools.partial(_kernel_planes, nb_c=nb_c,
                                   has_scale=has_scale,
                                   row_axis=_row_axis(order))
        in_specs = [
            pl.BlockSpec((m_tile, bc), x_map),
            pl.BlockSpec((1, 1, r_keep, c_keep), tile_i),
            pl.BlockSpec((1, 1, bc, c_keep), tile_i),
            pl.BlockSpec((1, 1, r_keep, br), tile_i),
        ]
        operands = [x, packed.vals, plan.gather_planes, plan.scatter_planes]
    else:
        kernel = functools.partial(
            _kernel_idx, nb_c=nb_c, block_rows=br, block_cols=bc,
            has_scale=has_scale, row_axis=_row_axis(order))
        in_specs = [
            pl.BlockSpec((m_tile, bc), x_map),
            pl.BlockSpec((1, 1, r_keep, c_keep), tile_i),
            pl.BlockSpec((1, nb_c, r_keep), idx_i),
            pl.BlockSpec((1, nb_c, c_keep), idx_i),
        ]
        operands = [x, packed.vals, packed.row_idx, packed.col_idx]
    if has_scale:
        in_specs.append(pl.BlockSpec((nb_r, nb_c), lambda *g: (0, 0)))
        operands.append(plan.block_scales)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m_tile, br), out_map),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((m_tile, br), jnp.float32)],
        interpret=interpret,
        name="bcr_spmm",
    )(*operands)
    return out


# ---------------------------------------------------------------------------
# Grouped projections: G packed weights sharing one activation
# ---------------------------------------------------------------------------


def _grouped_emit(o_ref, acc_ref, bias_ref, epilogue):
    """Fused epilogue at the last contraction step: per-member bias add
    (fp32, straight off the accumulator) and optionally the gate/up
    activation — the elementwise passes the model otherwise dispatches
    separately after the matmul.

    ``epilogue``: None → emit every member ``(G, M_t, br)``; ``"swiglu"``
    → emit ``silu(acc[0]) * acc[1]`` as one ``(M_t, br)`` block (valid
    per-block: the accumulator is already dense in output coordinates, and
    SwiGLU is elementwise over N).
    """
    acc = acc_ref[...]
    if bias_ref is not None:
        acc = acc + bias_ref[...].astype(jnp.float32)[:, None, :]
    if epilogue == "swiglu":
        o_ref[...] = (jax.nn.silu(acc[0]) * acc[1]).astype(o_ref.dtype)
    else:
        o_ref[...] = acc.astype(o_ref.dtype)


def _grouped_kernel_idx(x_ref, vals_ref, row_ref, col_ref, *rest,
                        nb_c: int, block_rows: int, block_cols: int,
                        group: int, has_scale: bool, has_bias: bool,
                        epilogue, row_axis: int):
    scale_ref = rest[0] if has_scale else None
    bias_ref = rest[int(has_scale)] if has_bias else None
    o_ref, acc_ref = rest[-2], rest[-1]
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                      # DMA'd ONCE for the whole group
    i = pl.program_id(row_axis)
    for g in range(group):              # static unroll
        gather, scatter_t = _onehots(col_ref[g, 0, pl.ds(j, 1), :],
                                     row_ref[g, 0, pl.ds(j, 1), :],
                                     block_rows, block_cols, x.dtype)
        scale = (_block_scale(scale_ref[g, pl.ds(i, 1), :], j)
                 if has_scale else None)
        acc_ref[g] += _block_update(x, vals_ref[g, 0, 0], gather, scatter_t,
                                    scale, scatter_t=True)

    @pl.when(j == nb_c - 1)
    def _emit():
        _grouped_emit(o_ref, acc_ref, bias_ref, epilogue)


def _grouped_kernel_planes(x_ref, vals_ref, gpl_ref, spl_ref, *rest,
                           nb_c: int, group: int, has_scale: bool,
                           has_bias: bool, epilogue, row_axis: int):
    scale_ref = rest[0] if has_scale else None
    bias_ref = rest[int(has_scale)] if has_bias else None
    o_ref, acc_ref = rest[-2], rest[-1]
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    i = pl.program_id(row_axis)
    for g in range(group):
        gather = gpl_ref[g, 0, 0].astype(x.dtype)
        scatter = spl_ref[g, 0, 0].astype(jnp.float32)
        scale = (_block_scale(scale_ref[g, pl.ds(i, 1), :], j)
                 if has_scale else None)
        acc_ref[g] += _block_update(x, vals_ref[g, 0, 0], gather, scatter,
                                    scale)

    @pl.when(j == nb_c - 1)
    def _emit():
        _grouped_emit(o_ref, acc_ref, bias_ref, epilogue)


@functools.partial(jax.jit,
                   static_argnames=("m_tile", "epilogue", "interpret"))
def bcr_spmm_grouped(
    x: jax.Array,
    grouped,                       # plan.GroupedTBCRC
    *,
    bias: Optional[jax.Array] = None,      # (G, N)
    epilogue: Optional[str] = None,        # None | "swiglu"
    m_tile: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """``y[G, M, N] = x[M, K] @ W_g.T`` for G same-shaped packed weights.

    One grid step serves every group member: ``x``'s block (and the VMEM
    residency the gathered form rides on) is shared, so activation HBM
    traffic and grid-step overhead are both amortized G-fold vs G separate
    ``bcr_spmm`` calls. ``bias``/``epilogue`` fuse the post-matmul
    elementwise pass into the emit step (off the fp32 VMEM accumulator, no
    extra HBM round-trip); ``epilogue="swiglu"`` collapses a G=2 gate/up
    group into its ``(M, N)`` activated hidden.
    """
    m, k = x.shape
    n = grouped.shape[0]
    br, bc = grouped.block_shape
    g_size, nb_r, nb_c, r_keep, c_keep = grouped.vals.shape
    if grouped.shape[1] != k:
        raise ValueError(f"x K dim {k} != packed K dim {grouped.shape[1]}")

    plan = grouped.plan
    if m_tile is None and plan is not None and plan.m_tile:
        m_tile = plan.m_tile if m % plan.m_tile == 0 else None
    m_tile = m_tile or m
    if m % m_tile:
        raise ValueError(f"M={m} not divisible by m_tile={m_tile}")
    m_steps = m // m_tile
    order = plan.grid_order if plan is not None else "mij"
    use_planes = plan is not None and plan.use_planes

    if epilogue == "swiglu" and g_size != 2:
        raise ValueError(f"swiglu epilogue needs a gate/up pair, got "
                         f"group_size={g_size}")
    has_scale = plan is not None and plan.block_scales is not None

    grid, norm, x_map, out_map3 = _grid_and_maps(order, m_steps, nb_r, nb_c)
    tile_i = lambda *g: (0, norm(*g)[1], norm(*g)[2], 0, 0)
    out_map = lambda *g: (0,) + out_map3(*g)

    if use_planes:
        kernel = functools.partial(_grouped_kernel_planes, nb_c=nb_c,
                                   group=g_size, has_scale=has_scale,
                                   has_bias=bias is not None,
                                   epilogue=epilogue,
                                   row_axis=_row_axis(order))
        in_specs = [
            pl.BlockSpec((m_tile, bc), x_map),
            pl.BlockSpec((g_size, 1, 1, r_keep, c_keep), tile_i),
            pl.BlockSpec((g_size, 1, 1, bc, c_keep), tile_i),
            pl.BlockSpec((g_size, 1, 1, r_keep, br), tile_i),
        ]
        operands = [x, grouped.vals, plan.gather_planes, plan.scatter_planes]
    else:
        kernel = functools.partial(
            _grouped_kernel_idx, nb_c=nb_c, block_rows=br, block_cols=bc,
            group=g_size, has_scale=has_scale, has_bias=bias is not None,
            epilogue=epilogue, row_axis=_row_axis(order))
        idx_i = lambda *g: (0, norm(*g)[1], 0, 0)
        in_specs = [
            pl.BlockSpec((m_tile, bc), x_map),
            pl.BlockSpec((g_size, 1, 1, r_keep, c_keep), tile_i),
            pl.BlockSpec((g_size, 1, nb_c, r_keep), idx_i),
            pl.BlockSpec((g_size, 1, nb_c, c_keep), idx_i),
        ]
        operands = [x, grouped.vals, grouped.row_idx, grouped.col_idx]
    if has_scale:
        in_specs.append(pl.BlockSpec((g_size, nb_r, nb_c),
                                     lambda *g: (0, 0, 0)))
        operands.append(plan.block_scales)
    if bias is not None:
        in_specs.append(pl.BlockSpec(
            (g_size, br), lambda *g: (0, norm(*g)[1])))
        operands.append(bias)

    if epilogue == "swiglu":
        out_spec = pl.BlockSpec((m_tile, br), out_map3)
        out_shape = jax.ShapeDtypeStruct((m, n), x.dtype)
    else:
        out_spec = pl.BlockSpec((g_size, m_tile, br), out_map)
        out_shape = jax.ShapeDtypeStruct((g_size, m, n), x.dtype)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((g_size, m_tile, br), jnp.float32)],
        interpret=interpret,
        name="bcr_spmm_grouped",
    )(*operands)
    return out
