"""Tensor-parallel serving: shard the engine's params + KV pool over a mesh.

The sharded engine runs each of its four jit'd programs (prefill / decode /
append / verify) as ``jit(shard_map(body, mesh))`` over a 1-D ``("model",)``
mesh. The contract that drives every layout choice here is **bit-exact
equivalence with the single-device engine** — greedy tokens must match
token-for-token, which rules out any collective that changes fp32 summation
order. Hence:

* **Every projection is column-parallel** (output dim sharded, input
  replicated): each shard computes full-``K`` dot products for its slice of
  output rows — identical arithmetic to the single-device program — and the
  activation is re-replicated with an all-gather (pure data movement; see
  ``repro.runtime.collectives.tp_all_gather``). Row-parallel + psum would
  halve the gather traffic but splits the reduction, changing summation
  order and breaking bit-exactness.
* **Packed BCR weights shard along output row blocks**: the ``BCRPlan``
  flat take/scatter vectors are rebuilt at shard time so each device holds
  a self-contained sub-plan in its local index space
  (``repro.kernels.plan.split_packed`` / ``split_grouped``) and runs the
  unmodified spmm kernels. The prepared *global* arrays are laid out so a
  plain ``PartitionSpec`` slice hands each device exactly its sub-plan.
* **Attention is head-parallel**: Q/K/V column shards are whole head
  groups (``num_heads % tp == 0`` enforced), per-head softmax/dots are
  untouched, and the paged KV pool (+ int8 scale pools) shards along its
  ``Hkv`` axis. Block tables stay replicated host-side, so every page-pool
  invariant — null page 0, CoW, prefix reuse, ``truncate`` rollback —
  holds per shard by construction.
* Weights whose output dim does not divide the mesh (e.g. an odd vocab)
  **fall back to replicated**; the layers' shape-driven ``maybe_gather``
  then no-ops. Attention projections are the exception — their shards must
  align with the head split, so an unshardable attention projection is a
  build-time error, not a silent fallback.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.bcrc import TBCRC
from repro.kernels.plan import (BCRPlan, GroupedTBCRC, split_grouped,
                                split_packed, splittable_packed, _member)

PyTree = Any

AXIS = "model"

# attention projections MUST shard (their slices are the head groups the
# per-shard KV pool expects); anything else may fall back to replicated
_ATTN_PROJ_KEYS = ("wq", "wk", "wv", "wqkv", "wkv")

# the only dicts whose DENSE ``w`` may column-shard: linears applied via
# linear_apply. Anything else holding a 2-D "w" (the embedding table,
# whose rows are indexed by token id, not matmul'd) must stay replicated.
_LINEAR_KEYS = _ATTN_PROJ_KEYS + ("wo", "wg", "wi", "wgi", "lm_head")


# ---------------------------------------------------------------------------
# Gating + config localization
# ---------------------------------------------------------------------------


def shardable(cfg: ModelConfig, tp: int, page_size: int) -> Optional[str]:
    """None if the sharded engine supports this config at mesh ``tp``,
    else the human-readable reason it cannot."""
    if tp <= 1:
        return None
    if page_size <= 0:
        return "sharded serving needs a paged KV pool (--page-size > 0)"
    if cfg.family not in ("dense", "vlm"):
        return (f"family {cfg.family!r} not supported by the sharded "
                f"engine (pure-attention dense/vlm only)")
    if cfg.num_experts:
        return "MoE FFNs are not supported by the sharded engine"
    if cfg.attn_period:
        return "hybrid attn/mamba stacks are not supported sharded"
    if cfg.num_heads % tp:
        return f"num_heads={cfg.num_heads} not divisible by mesh {tp}"
    if cfg.num_kv_heads % tp:
        return f"num_kv_heads={cfg.num_kv_heads} not divisible by mesh {tp}"
    return None


def localize_cfg(cfg: ModelConfig, tp: int) -> ModelConfig:
    """The config the model body sees INSIDE shard_map: per-shard head
    counts, ``tp_axis`` set so layers re-replicate after each projection.
    ``d_model``/``d_ff``/``vocab_size`` stay full — the apply path derives
    working dims from the (sharded) weights themselves."""
    return dataclasses.replace(
        cfg, num_heads=cfg.num_heads // tp,
        num_kv_heads=cfg.num_kv_heads // tp, tp_axis=AXIS)


def make_model_mesh(tp: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < tp:
        raise ValueError(
            f"mesh_model={tp} but only {len(devs)} devices visible "
            f"(CPU testing: XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={tp})")
    return Mesh(np.array(devs[:tp]), (AXIS,))


def per_device_kv_bytes(total_bytes: int, tp: int) -> int:
    """Aggregate KV traffic → per-device traffic under an ``Hkv``-sharded
    pool: every page leaf splits along its head axis, nothing is
    replicated, so each device moves ``1/tp`` of the bytes. The engine
    reports BOTH (``kv_bytes_read`` aggregate, ``kv_bytes_read_device``
    per-device) so multi-device runs don't overcount bandwidth."""
    return total_bytes // max(tp, 1)


# ---------------------------------------------------------------------------
# Param preparation: one GLOBAL tree whose PartitionSpec slices are
# self-contained per-shard sub-programs
# ---------------------------------------------------------------------------


def _axspec(axis: int) -> P:
    return P(*([None] * axis), AXIS)


def _replicated(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda _: P(), tree)


def _cat(parts: Sequence[Optional[jax.Array]], ax: int):
    if any(p is None for p in parts):
        return None
    return jnp.concatenate(list(parts), axis=ax)


def _prep_packed(packed: TBCRC, tp: int) -> Tuple[TBCRC, TBCRC]:
    """(prepared, specs): global arrays with shard-slicable plan flats and
    LOCAL aux shape. Inside shard_map the unflattened TBCRC then has local
    leaves + local aux — a well-formed local pack the kernels run as-is."""
    shards = split_packed(packed, tp)
    n, k = packed.shape
    plan = packed.plan
    prep_plan = BCRPlan(
        gather_cols=_cat([s.plan.gather_cols for s in shards], -1),
        scatter_rows=_cat([s.plan.scatter_rows for s in shards], -1),
        gather_planes=plan.gather_planes if plan is not None else None,
        scatter_planes=plan.scatter_planes if plan is not None else None,
        block_scales=plan.block_scales if plan is not None else None,
        m_tile=plan.m_tile if plan is not None else None,
        grid_order=plan.grid_order if plan is not None else "mij",
        group_size=plan.group_size if plan is not None else 1)
    prepared = TBCRC(vals=packed.vals, row_idx=packed.row_idx,
                     col_idx=packed.col_idx, shape=(n // tp, k),
                     block_shape=packed.block_shape, plan=prep_plan)
    nbr_ax = packed.vals.ndim - 4

    def opt(a, axis):
        return _axspec(axis % a.ndim) if a is not None else None
    spec_plan = BCRPlan(
        gather_cols=_axspec(prep_plan.gather_cols.ndim - 1),
        scatter_rows=_axspec(prep_plan.scatter_rows.ndim - 1),
        gather_planes=opt(prep_plan.gather_planes, -4),
        scatter_planes=opt(prep_plan.scatter_planes, -4),
        block_scales=opt(prep_plan.block_scales, -2),
        m_tile=prep_plan.m_tile, grid_order=prep_plan.grid_order,
        group_size=prep_plan.group_size)
    specs = TBCRC(vals=_axspec(nbr_ax), row_idx=_axspec(nbr_ax),
                  col_idx=_axspec(nbr_ax), shape=(n // tp, k),
                  block_shape=packed.block_shape, plan=spec_plan)
    return prepared, specs


def _prep_grouped(grouped: GroupedTBCRC, tp: int,
                  ) -> Tuple[GroupedTBCRC, GroupedTBCRC]:
    """Like :func:`_prep_packed` for fused projection groups. The fused
    flats are g-major and do NOT slice along the output axis, so the
    prepared global flats are the shard-major concatenation of each
    shard's locally-rebuilt (member-offset ``g·N/tp``) vectors."""
    shards = split_grouped(grouped, tp)
    n, k = grouped.shape
    plan = grouped.plan
    prep_plan = BCRPlan(
        gather_cols=_cat([s.plan.gather_cols for s in shards], -1),
        scatter_rows=_cat([s.plan.scatter_rows for s in shards], -1),
        gather_planes=plan.gather_planes if plan is not None else None,
        scatter_planes=plan.scatter_planes if plan is not None else None,
        block_scales=plan.block_scales if plan is not None else None,
        m_tile=plan.m_tile if plan is not None else None,
        grid_order=plan.grid_order if plan is not None else "mij",
        group_size=grouped.group_size)
    prepared = GroupedTBCRC(
        vals=grouped.vals, row_idx=grouped.row_idx, col_idx=grouped.col_idx,
        plan=prep_plan, shape=(n // tp, k),
        block_shape=grouped.block_shape, group_size=grouped.group_size)
    nbr_ax = grouped.vals.ndim - 4   # after the member axis

    def opt(a, axis):
        return _axspec(axis % a.ndim) if a is not None else None
    spec_plan = BCRPlan(
        gather_cols=_axspec(prep_plan.gather_cols.ndim - 1),
        scatter_rows=_axspec(prep_plan.scatter_rows.ndim - 1),
        gather_planes=opt(prep_plan.gather_planes, -4),
        scatter_planes=opt(prep_plan.scatter_planes, -4),
        block_scales=opt(prep_plan.block_scales, -2),
        m_tile=prep_plan.m_tile, grid_order=prep_plan.grid_order,
        group_size=prep_plan.group_size)
    specs = GroupedTBCRC(
        vals=_axspec(nbr_ax), row_idx=_axspec(nbr_ax),
        col_idx=_axspec(nbr_ax), plan=spec_plan, shape=(n // tp, k),
        block_shape=grouped.block_shape, group_size=grouped.group_size)
    return prepared, specs


def prepare_params(params: PyTree, tp: int) -> Tuple[PyTree, PyTree]:
    """Walk a (possibly packed/fused/quantized) params tree and return
    ``(prepared, specs)``: the global tree plus the PartitionSpec tree that
    device_put/shard_map use to hand each device its column-parallel slice.

    Dense linears shard their output dim when divisible, else replicate.
    Packed/grouped linears go through the plan splitters. Attention
    projections must shard (head alignment) — unshardable ones raise.
    """
    def walk(node: PyTree, key: Optional[str] = None):
        if isinstance(node, dict):
            if "w_packed" in node and isinstance(node["w_packed"], TBCRC):
                packed = node["w_packed"]
                reason = splittable_packed(packed, tp)
                out, spec = dict(node), _replicated(node)
                if reason is None:
                    out["w_packed"], spec["w_packed"] = _prep_packed(
                        packed, tp)
                    if "b" in node:
                        spec["b"] = _axspec(node["b"].ndim - 1)
                    return out, spec
                if key in _ATTN_PROJ_KEYS:
                    raise ValueError(
                        f"attention projection {key!r} cannot shard over "
                        f"mesh {tp}: {reason} (pick a bcr_block whose row "
                        f"blocks divide the mesh, or serve dense)")
                return out, spec
            if "w_group" in node and isinstance(node["w_group"],
                                                GroupedTBCRC):
                grouped = node["w_group"]
                reason = splittable_packed(_member(grouped, 0), tp)
                out, spec = dict(node), _replicated(node)
                if reason is None:
                    out["w_group"], spec["w_group"] = _prep_grouped(
                        grouped, tp)
                    if "b" in node:
                        spec["b"] = _axspec(node["b"].ndim - 1)
                    return out, spec
                if key in _ATTN_PROJ_KEYS:
                    raise ValueError(
                        f"fused attention projection {key!r} cannot shard "
                        f"over mesh {tp}: {reason}")
                return out, spec
            if ("w" in node and key in _LINEAR_KEYS
                    and not isinstance(node["w"], dict)):
                w = node["w"]
                n = w.shape[-2]
                spec = _replicated(node)
                if n % tp == 0:
                    spec["w"] = _axspec(w.ndim - 2)
                    if "b" in node:
                        spec["b"] = _axspec(node["b"].ndim - 1)
                elif key in _ATTN_PROJ_KEYS:
                    raise ValueError(
                        f"attention projection {key!r} output dim {n} not "
                        f"divisible by mesh {tp}")
                return dict(node), spec
            pairs = {k: walk(v, k) for k, v in node.items()}
            return ({k: p[0] for k, p in pairs.items()},
                    {k: p[1] for k, p in pairs.items()})
        if isinstance(node, list):
            pairs = [walk(v, key) for v in node]
            return [p[0] for p in pairs], [p[1] for p in pairs]
        if node is None:
            return None, None
        return node, P()

    return walk(params)


# ---------------------------------------------------------------------------
# Cache specs: which axis of each cache/pool leaf is Hkv (discovered by
# probing init_cache shapes at two num_kv_heads values — the same
# shape-diff idiom PagedSlotPool uses for its batch/page axes)
# ---------------------------------------------------------------------------


def cache_axes(cfg: ModelConfig, batch: int, capacity: int, *,
               kv_pages: int = 0, page_size: int = 0) -> PyTree:
    """Per-leaf index of the ``Hkv`` axis (−1 → replicated leaf)."""
    from repro.models import causal_lm

    def shapes(c):
        return jax.eval_shape(lambda: causal_lm.init_cache(
            c, batch, capacity, kv_pages=kv_pages, page_size=page_size))

    a = shapes(cfg)
    b = shapes(dataclasses.replace(cfg, num_kv_heads=cfg.num_kv_heads * 2))

    def ax(la, lb):
        diffs = [i for i, (x, y) in enumerate(zip(la.shape, lb.shape))
                 if x != y]
        assert len(diffs) <= 1, (la.shape, lb.shape)
        return diffs[0] if diffs else -1

    return jax.tree_util.tree_map(ax, a, b)


def cache_specs(cfg: ModelConfig, batch: int, capacity: int, *,
                kv_pages: int = 0, page_size: int = 0) -> PyTree:
    """PartitionSpec tree for a cache of this shape: ``Hkv`` leaves split
    over the mesh (KV codes AND their int8 scale siblings — the scale
    leaf's own ``Hkv`` axis diffs in the same probe, so scales shard with
    their codes for free), everything else replicated. The same axis
    indices serve both the persistent head-major pool layout (``Hkv`` at
    axis −3 of its k/v leaves) and the prefill-output layout (axis −2),
    probed per leaf rather than assumed."""
    axes = cache_axes(cfg, batch, capacity, kv_pages=kv_pages,
                      page_size=page_size)
    return jax.tree_util.tree_map(
        lambda ax: P() if ax < 0 else _axspec(ax), axes)


def placed(tree: PyTree, specs: PyTree, mesh: Mesh) -> PyTree:
    """device_put every leaf with its NamedSharding (sharded engine build:
    params once, the fresh pool cache once — steady-state placement then
    flows from the programs' out_specs)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)


# ---------------------------------------------------------------------------
# Program wrapper: jit(shard_map) with per-static-flag variants
# ---------------------------------------------------------------------------


class ShardedProgram:
    """``jit(shard_map(fn))`` standing in for ``jit(fn, static_argnames)``.

    Python-static flags can't cross a shard_map boundary, so each flag
    value gets its own closed-over body + jit; the call-site keyword
    dispatches between them (compiles lazily, exactly like the
    single-device engine's two-variant static_argnames jit).
    ``check_vma=False`` because replicated outputs (sampled tokens,
    logits after the lm_head gather) are replicated by construction —
    every shard computes the identical full array."""

    def __init__(self, fn: Callable, mesh: Mesh, in_specs: Sequence[Any],
                 out_specs: Any, *, static_name: Optional[str] = None,
                 donate_argnums: Tuple[int, ...] = ()):
        self.static_name = static_name

        def build(**kw):
            body = functools.partial(fn, **kw) if kw else fn
            sm = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=out_specs, check_vma=False)
            return jax.jit(sm, donate_argnums=donate_argnums)

        if static_name is None:
            self._variants: Dict[Any, Callable] = {None: build()}
        else:
            self._variants = {v: build(**{static_name: v})
                              for v in (False, True)}

    def __call__(self, *args, **kwargs):
        if self.static_name is None:
            assert not kwargs
            return self._variants[None](*args)
        flag = bool(kwargs.pop(self.static_name))
        assert not kwargs, kwargs
        return self._variants[flag](*args)
