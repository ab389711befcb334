"""Pure-jnp oracles for the BCR sparse-matmul kernels.

``bcr_spmm_ref`` is the semantic ground truth (dense reconstruction, one
einsum). ``bcr_spmm_gather_ref`` mirrors the kernel's gather → dense tile
matmul → scatter-add decomposition step by step and is used to localize
kernel bugs (same intermediate values, pure jnp).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bcrc import TBCRC, tbcrc_unpack


def bcr_spmm_ref(x: jax.Array, packed: TBCRC) -> jax.Array:
    """y[M, N] = x[M, K] @ W.T with W = dense reconstruction of ``packed``."""
    w = tbcrc_unpack(packed)  # (N, K)
    return jnp.dot(x, w.T.astype(x.dtype), preferred_element_type=jnp.float32).astype(x.dtype)


def bcr_spmm_packed_ref(x: jax.Array, packed: TBCRC) -> jax.Array:
    """Reconstruction-free CPU/GPU path: ``y = x @ W.T`` straight off the
    packed ``(nb_r, nb_c, R_keep, C_keep)`` vals.

    Uses the pack-time plan's flat index vectors: ONE ``jnp.take`` gathers
    every surviving activation, ONE batched einsum multiplies the dense
    kept tiles, ONE scatter-add places the partial products. Weight bytes
    and MXU flops scale with ``keep_frac``; no dense ``(N, K)`` tensor ever
    appears in the jitted step (the old ``bcr_spmm_ref`` rebuilt ``W``
    inside every decode step — the 0.79x-vs-dense regression).
    """
    plan = packed.plan
    if plan is None:
        raise ValueError("bcr_spmm_packed_ref needs a packed.plan "
                         "(tbcrc_pack attaches one; see kernels/plan.py)")
    m = x.shape[0]
    nb_r, nb_c, r_keep, c_keep = packed.vals.shape
    n = packed.shape[0]
    xg = jnp.take(x, plan.gather_cols, axis=1)        # (M, nb_r·nb_c·Ck)
    xg = xg.reshape(m, nb_r, nb_c, c_keep)
    part = jnp.einsum("mijc,ijrc->mijr", xg.astype(jnp.float32),
                      packed.vals.astype(jnp.float32))
    if plan.block_scales is not None:
        # int8 tiles: fold the per-block scale into the fp32 partial
        # before the scatter-add (exact — the scatter is 0/1)
        part = part * plan.block_scales[None, :, :, None]
    y = jnp.zeros((m, n), jnp.float32)
    y = y.at[:, plan.scatter_rows].add(part.reshape(m, -1))
    return y.astype(x.dtype)


def grouped_epilogue(y: jax.Array, bias, epilogue: str | None,
                     out_dtype) -> jax.Array:
    """Shared epilogue semantics for the grouped paths: fp32 ``y`` is
    ``(..., G, N)``; bias ``(G, N)`` adds before the activation.

    ``epilogue``:
      * ``None``     — plain (bias-added) group outputs, ``(..., G, N)``
      * ``"swiglu"`` — ``silu(y[0]) * y[1]`` collapsing G=2 gate/up into
        one ``(..., N)`` hidden — the elementwise pass the MLP otherwise
        runs after the matmul dispatch.
    """
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if epilogue == "swiglu":
        assert y.shape[-2] == 2, "swiglu epilogue needs a gate/up pair"
        y = jax.nn.silu(y[..., 0, :]) * y[..., 1, :]
    elif epilogue is not None:
        raise ValueError(f"unknown epilogue {epilogue!r}")
    return y.astype(out_dtype)


def bcr_spmm_grouped_ref(x: jax.Array, grouped, bias=None,
                         epilogue: str | None = None) -> jax.Array:
    """Grouped-projection ref path: G same-shaped packed weights sharing
    ``x`` (Q/K/V, gate/up) in one take + one einsum + one scatter-add.

    Returns ``(M, G, N)``; the plan's scatter vector offsets member ``g``
    by ``g·N`` so all partial products land in one output buffer. ``bias``
    ``(G, N)`` and the activation ``epilogue`` fuse into the same fp32
    accumulator pass (no separate elementwise dispatch afterwards); with
    ``epilogue="swiglu"`` the result is ``(M, N)``.
    """
    plan = grouped.plan
    m = x.shape[0]
    g, nb_r, nb_c, r_keep, c_keep = grouped.vals.shape
    n = grouped.shape[0]
    xg = jnp.take(x, plan.gather_cols, axis=1)
    xg = xg.reshape(m, g, nb_r, nb_c, c_keep)
    part = jnp.einsum("mgijc,gijrc->mgijr", xg.astype(jnp.float32),
                      grouped.vals.astype(jnp.float32))
    if plan.block_scales is not None:
        part = part * plan.block_scales[None, :, :, :, None]
    y = jnp.zeros((m, g * n), jnp.float32)
    y = y.at[:, plan.scatter_rows].add(part.reshape(m, -1))
    return grouped_epilogue(y.reshape(m, g, n), bias, epilogue, x.dtype)


def bcr_spmm_gather_ref(x: jax.Array, packed: TBCRC) -> jax.Array:
    """Block-by-block gather/matmul/scatter — mirrors the Pallas kernel."""
    m, k = x.shape
    n = packed.shape[0]
    br, bc = packed.block_shape
    nb_r, nb_c, r_keep, c_keep = packed.vals.shape

    xb = x.reshape(m, nb_c, bc)

    def block_row(i, y):
        acc = jnp.zeros((m, br), jnp.float32)

        def block_col(j, acc):
            cols = packed.col_idx[i, j]                     # (C_keep,)
            xg = jnp.take(xb[:, j, :], cols, axis=1)        # (M, C_keep)
            w = packed.vals[i, j]                           # (R_keep, C_keep)
            part = jnp.dot(xg.astype(jnp.float32), w.T.astype(jnp.float32))
            if packed.plan is not None \
                    and packed.plan.block_scales is not None:
                part = part * packed.plan.block_scales[i, j]
            rows = packed.row_idx[i, j]                     # (R_keep,)
            return acc.at[:, rows].add(part)

        acc = jax.lax.fori_loop(0, nb_c, block_col, acc)
        return jax.lax.dynamic_update_slice(y, acc.astype(y.dtype), (0, i * br))

    y = jnp.zeros((m, n), x.dtype)
    return jax.lax.fori_loop(0, nb_r, block_row, y)


def _gather_dequant(pages, scale, block_tables, b, l, hkv, d):
    """Gather head-major table pages ``(n_pages, Hkv, page_size, D)`` into
    a contiguous (B, L, Hkv, D) history, dequantizing off the sibling
    ``(n_pages, Hkv, page_size)`` scale pool when the pages hold int8
    codes."""
    k = jnp.take(pages, block_tables, axis=0)       # (B, cols, Hkv, ps, D)
    k = jnp.swapaxes(k, 2, 3).reshape(b, l, hkv, d)
    if scale is not None:
        sc = jnp.take(scale, block_tables, axis=0)
        sc = jnp.swapaxes(sc, 2, 3).reshape(b, l, hkv)
        k = k.astype(jnp.float32) * sc.astype(jnp.float32)[..., None]
    return k


def paged_decode_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_tables: jax.Array,
                               cache_len: jax.Array, k_scale=None,
                               v_scale=None) -> jax.Array:
    """Pure-JAX oracle for the paged flash-decode kernel: gather each
    slot's table pages, then masked single-step attention.

    q ``(B, 1, H, D)``; pages ``(n_pages, Hkv, page_size, D)``; tables
    ``(B, n_cols)``; cache_len ``(B,)`` counts valid positions including
    the step's new token. With ``k_scale``/``v_scale`` the pages hold
    int8 codes dequantized off the ``(n_pages, Hkv, page_size)`` scale
    pools after the gather. Bytes read scale with the table WIDTH handed
    in (the engine buckets it to the longest live slot) — the Pallas
    kernel further skips each slot's dead columns (never copied).
    """
    b, s, h, d = q.shape
    assert s == 1
    n_pages, hkv, page_size, _ = k_pages.shape
    g = h // hkv
    n_cols = block_tables.shape[1]
    l = n_cols * page_size
    # (B, n_cols, Hkv, page_size, D) -> (B, L, Hkv, D) contiguous history
    k = _gather_dequant(k_pages, k_scale, block_tables, b, l, hkv, d)
    v = _gather_dequant(v_pages, v_scale, block_tables, b, l, hkv, d)
    qg = q.reshape(b, hkv, g, d).astype(k.dtype)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    valid = jnp.arange(l)[None] < jnp.asarray(cache_len)[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def paged_prefill_append_ref(q: jax.Array, k_pages: jax.Array,
                             v_pages: jax.Array, block_tables: jax.Array,
                             prefix_len: jax.Array, total_len: jax.Array,
                             k_scale=None, v_scale=None) -> jax.Array:
    """Pure-JAX oracle for the paged prefill-append kernel: gather each
    slot's table pages, then causally masked attention for an S-row query
    block whose row ``i`` sits at absolute position ``prefix_len[b] + i``.

    q ``(B, S, H, D)``; pages ``(n_pages, Hkv, page_size, D)``; tables
    ``(B, n_cols)``; ``prefix_len`` counts cached positions before the
    suffix, ``total_len = prefix_len + true suffix length`` bounds the
    live positions. The suffix K/V must already be scattered into the
    table pages (the model's append path writes them first) — both the
    ref and the Pallas kernel read pages only. Rows at/past the true
    suffix length produce garbage that the caller discards.
    """
    b, s, h, d = q.shape
    n_pages, hkv, page_size, _ = k_pages.shape
    g = h // hkv
    l = block_tables.shape[1] * page_size
    k = _gather_dequant(k_pages, k_scale, block_tables, b, l, hkv, d)
    v = _gather_dequant(v_pages, v_scale, block_tables, b, l, hkv, d)
    qg = q.reshape(b, s, hkv, g, d).astype(k.dtype)
    logits = jnp.einsum("bshgd,bkhd->bhgsk", qg, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    qpos = jnp.asarray(prefix_len, jnp.int32)[:, None] + jnp.arange(s)[None]
    kpos = jnp.arange(l)
    valid = ((kpos[None, None] <= qpos[:, :, None])
             & (kpos[None, None] < jnp.asarray(total_len)[:, None, None]))
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgsk,bkhd->bshgd", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, d).astype(q.dtype)


def masked_dense_ref(x: jax.Array, w: jax.Array, mask: jax.Array) -> jax.Array:
    """Training-path reference: dense matmul with a hard BCR mask."""
    wm = (w * mask.astype(w.dtype))
    return jnp.dot(x, wm.T, preferred_element_type=jnp.float32).astype(x.dtype)
