"""Int8 quantized serving: round-trip bounds, quantized kernel vs ref
(GQA / partial pages / ragged lengths), scale-pool lifecycle under CoW
and truncation, int8 BCR weights vs the dequantized dense oracle, and
engine-level int8-vs-fp greedy divergence at a fixed seed."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core import BCRSpec, tbcrc_pack, tbcrc_unpack
from repro.kernels import bcr_matmul, bcr_spmm_ref
from repro.kernels.plan import attach_plan, quantize_packed
from repro.kernels.quant import (INT8_MAX, dequantize_blocks,
                                 dequantize_rows, quantize_blocks,
                                 quantize_rows)
from repro.kernels.paged_decode_attention import (
    paged_decode_attention, paged_kv_bytes, paged_prefill_append_attention)
from repro.kernels.ref import (paged_decode_attention_ref,
                               paged_prefill_append_ref)
from repro.models.api import model_fns
from repro.serving import EngineConfig, InferenceEngine
from repro.serving.kv_slots import PagedSlotPool


# ---------------------------------------------------------------------------
# Round-trip error bounds
# ---------------------------------------------------------------------------


def test_quantize_rows_roundtrip_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(33, 5, 64)) * 3.0, jnp.float32)
    codes, scale = quantize_rows(x)
    assert codes.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    err = jnp.abs(dequantize_rows(codes, scale) - x)
    # symmetric round-to-nearest: per-element error ≤ scale/2
    assert bool(jnp.all(err <= scale[..., None] * 0.5 + 1e-7))
    # relative to the row absmax that set the scale: ≤ 1/254
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    assert float(jnp.max(err / amax)) <= 1.0 / (2 * INT8_MAX) + 1e-6


def test_quantize_rows_zero_rows():
    x = jnp.zeros((4, 2, 16), jnp.float32)
    codes, scale = quantize_rows(x)
    assert bool(jnp.all(codes == 0)) and bool(jnp.all(scale > 0))
    assert bool(jnp.all(dequantize_rows(codes, scale) == 0))


def test_quantize_blocks_roundtrip_bound():
    rng = np.random.default_rng(1)
    vals = jnp.asarray(rng.normal(size=(3, 2, 16, 8)) * 0.2, jnp.float32)
    codes, scales = quantize_blocks(vals)
    assert codes.dtype == jnp.int8 and scales.shape == vals.shape[:-2]
    err = jnp.abs(dequantize_blocks(codes, scales) - vals)
    assert bool(jnp.all(err <= scales[..., None, None] * 0.5 + 1e-7))


# ---------------------------------------------------------------------------
# Quantized paged kernels vs scale-aware refs
# ---------------------------------------------------------------------------


def _quantized_paged_case(lens, page_size, hkv=2, g=4, d=64, seed=0):
    """GQA pages (g query heads per kv head) quantized per-row, plus the
    fp32 dequantized copies the reference oracle consumes."""
    rng = np.random.default_rng(seed)
    b = len(lens)
    n_cols = max(-(-int(l) // page_size) for l in lens) or 1
    n_pages = 1 + b * n_cols
    kf = jnp.asarray(rng.normal(size=(n_pages, hkv, page_size, d)),
                     jnp.float32)
    vf = jnp.asarray(rng.normal(size=(n_pages, hkv, page_size, d)),
                     jnp.float32)
    kc, ks = quantize_rows(kf)
    vc, vs = quantize_rows(vf)
    bt = np.zeros((b, n_cols), np.int32)
    pid = 1
    for i, l in enumerate(lens):
        for p in range(-(-int(l) // page_size)):
            bt[i, p] = pid
            pid += 1
    q = jnp.asarray(rng.normal(size=(b, 1, hkv * g, d)), jnp.float32)
    return (q, kc, vc, ks, vs, jnp.asarray(bt), jnp.asarray(lens, jnp.int32),
            dequantize_rows(kc, ks), dequantize_rows(vc, vs))


@pytest.mark.parametrize("lens,page_size", [
    ([3, 17, 64, 50], 16),    # partial pages + ragged
    ([1, 5], 8),              # single-page shorties
    ([32, 32, 32], 16),       # exact page boundaries
    ([300, 3, 129, 40], 8),   # 16 pages a kernel step, ends mid-step
    ([70, 200], 64),          # 2 pages a step, a table of 4 columns
])
def test_quantized_decode_kernel_matches_ref(lens, page_size):
    # head dims of pipelined (64) and hand-copied (128) pages
    for d in (64, 128):
        q, kc, vc, ks, vs, bt, lv, kd, vd = _quantized_paged_case(
            lens, page_size, d=d)
        ref = paged_decode_attention_ref(q, kc, vc, bt, lv,
                                         k_scale=ks, v_scale=vs)
        # scale-aware ref equals the fp ref on the dequantized cache
        ref_fp = paged_decode_attention_ref(q, kd, vd, bt, lv)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(ref_fp),
                                   atol=1e-5, rtol=1e-5)
        got = paged_decode_attention(q, kc, vc, bt, lv, k_scale=ks,
                                     v_scale=vs, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_quantized_prefill_append_kernel_matches_ref():
    for case in (([3, 17, 64, 50], [0, 9, 56, 50], [6, 17, 64, 58], 8),
                 # suffixes whose causal mask crosses the 128-position
                 # kernel step
                 ([140, 200], [120, 126], [140, 136], 20)):
        for d in (64, 128):      # pipelined and hand-copied pages
            _check_quantized_append(*case, d)


def _check_quantized_append(lens, plen, tlen, s, d):
    page_size = 16
    q1, kc, vc, ks, vs, bt, _, kd, vd = _quantized_paged_case(lens, page_size,
                                                              d=d)
    b, _, h, d = q1.shape
    rng = np.random.default_rng(7)
    qs = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    plen = jnp.asarray(plen, jnp.int32)
    tlen = jnp.asarray(tlen, jnp.int32)
    ref = paged_prefill_append_ref(qs, kc, vc, bt, plen, tlen,
                                   k_scale=ks, v_scale=vs)
    ref_fp = paged_prefill_append_ref(qs, kd, vd, bt, plen, tlen)
    got = paged_prefill_append_attention(qs, kc, vc, bt, plen, tlen,
                                         k_scale=ks, v_scale=vs,
                                         interpret=True)
    # rows at/past each slot's true suffix length are documented garbage
    valid = (jnp.arange(s)[None] < (tlen - plen)[:, None])[:, :, None, None]
    for other, tol in ((ref_fp, 1e-5), (ref, 2e-5)):
        err = jnp.abs(jnp.where(valid, got - other, 0.0)
                      if other is ref else
                      jnp.where(valid, ref - other, 0.0))
        assert float(err.max()) < tol


def test_paged_kv_bytes_counts_scales_and_dtype():
    full = paged_kv_bytes(np.asarray([16, 16]), page_size=16, hkv=2,
                          d=64, dtype_bytes=4)
    q = paged_kv_bytes(np.asarray([16, 16]), page_size=16, hkv=2,
                       d=64, dtype_bytes=1, scale_bytes=4)
    # int8 codes + one fp32 scale per row per kv head vs fp32 rows
    assert q / full == pytest.approx((64 * 1 + 4) / (64 * 4))


# ---------------------------------------------------------------------------
# Scale pools through the paged pool lifecycle (CoW, truncate)
# ---------------------------------------------------------------------------


def _quantized_pool(n_slots=2, capacity=64, page_size=8, n_pages=17):
    cfg = dataclasses.replace(get_smoke_config("llama3.2-1b"),
                              attn_impl="flash", kv_dtype="int8")
    fns = model_fns(cfg)
    return cfg, fns, PagedSlotPool(fns.init_cache, n_slots, capacity,
                                   page_size=page_size, n_pages=n_pages)


def _page_leaves(pool):
    leaves = jax.tree_util.tree_leaves(pool.cache)
    axes = jax.tree_util.tree_leaves(pool._page_axes)
    return [(l, ax) for l, ax in zip(leaves, axes) if ax >= 0]


def test_scale_pools_exist_and_share_page_index_space():
    _, _, pool = _quantized_pool()
    leaves = _page_leaves(pool)
    code = [l for l, _ in leaves if l.dtype == jnp.int8]
    scale = [l.shape for l, _ in leaves if l.dtype == jnp.float32]
    assert code and scale and len(code) == len(scale)
    for c in code:
        # every code pool has a sibling scale pool sans the head_dim axis
        assert c.shape[:-1] in scale


def test_copy_pages_moves_codes_and_scales_together():
    cfg, fns, pool = _quantized_pool()
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 12)),
                       jnp.int32)
    params = fns.init_params(jax.random.PRNGKey(0))
    _, pc = fns.prefill(params, {"tokens": toks})
    pool.insert_rows(pc, np.asarray([0, 1]), np.asarray([12, 12]))
    src = np.asarray([int(pool.table[0, 0])])
    dst = np.asarray([int(pool.free_pages() and 16)])  # a free page id
    pool.copy_pages(src, dst)
    for leaf, pax in _page_leaves(pool):
        a = jax.lax.index_in_dim(leaf, int(src[0]), pax, keepdims=False)
        b = jax.lax.index_in_dim(leaf, int(dst[0]), pax, keepdims=False)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_truncate_keeps_scale_consistency():
    cfg, fns, pool = _quantized_pool(n_slots=1, page_size=4)
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, 10)),
                       jnp.int32)
    params = fns.init_params(jax.random.PRNGKey(0))
    _, pc = fns.prefill(params, {"tokens": toks})
    pool.insert_rows(pc, np.asarray([0]), np.asarray([10]))
    before = {int(pool.table[0, c]) for c in range(int(pool._n_alloc[0]))}
    pool.truncate(0, 5)            # drop pages wholly past position 5
    assert pool.lens[0] == 5
    kept = {int(pool.table[0, c]) for c in range(int(pool._n_alloc[0]))}
    assert kept < before
    # surviving rows (codes AND scales share the clamped index map) intact:
    # decode through the pool still matches a fresh un-truncated prefill
    step = fns.decode_step(
        params, {"tokens": toks[:, 5:6],
                 "cache_len": jnp.asarray(pool.lens),
                 "block_tables": pool.device_tables()}, pool.cache)
    logits5, _ = step
    _, pc5 = fns.prefill(params, {"tokens": toks[:, :5]})
    pool2 = PagedSlotPool(fns.init_cache, 1, 64, page_size=4, n_pages=17)
    pool2.insert_rows(pc5, np.asarray([0]), np.asarray([5]))
    pool2.ensure(0, 6)
    ref, _ = fns.decode_step(
        params, {"tokens": toks[:, 5:6],
                 "cache_len": jnp.asarray(pool2.lens),
                 "block_tables": pool2.device_tables()}, pool2.cache)
    np.testing.assert_allclose(np.asarray(logits5), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Int8 BCR weights vs the dequantized dense oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["ref", "dense_ref", "interpret"])
def test_quantized_bcr_matmul_matches_dequantized_oracle(impl):
    n, k, block, keep = 64, 128, (16, 16), 0.25
    w = jax.random.normal(jax.random.PRNGKey(0), (n, k), jnp.float32)
    spec = BCRSpec(block_shape=block, keep_frac=keep, align=4)
    packed = quantize_packed(attach_plan(tbcrc_pack(w, spec)))
    assert packed.vals.dtype == jnp.int8
    assert packed.plan.block_scales is not None
    x = jax.random.normal(jax.random.PRNGKey(1), (8, k), jnp.float32)
    # tbcrc_unpack reconstructs the DEQUANTIZED weight: exact oracle
    y_oracle = x @ tbcrc_unpack(packed).T
    y = bcr_matmul(x, packed, impl=impl)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_oracle),
                               atol=1e-4, rtol=1e-4)


def test_quantized_bcr_weight_error_bounded():
    n, k = 128, 128
    w = jax.random.normal(jax.random.PRNGKey(2), (n, k), jnp.float32)
    spec = BCRSpec(block_shape=(32, 32), keep_frac=0.5, align=4)
    packed_fp = attach_plan(tbcrc_pack(w, spec))
    packed_q = quantize_packed(packed_fp)
    wd_fp = tbcrc_unpack(packed_fp)
    wd_q = tbcrc_unpack(packed_q)
    err = jnp.abs(wd_q - wd_fp)
    scales = packed_q.plan.block_scales
    assert float(err.max()) <= float(scales.max()) * 0.5 + 1e-7


# ---------------------------------------------------------------------------
# Engine: int8 KV + int8 weights vs fp, greedy divergence at fixed seed
# ---------------------------------------------------------------------------


def _divergence(a_seqs, b_seqs):
    div = tot = 0
    for a, b in zip(a_seqs, b_seqs):
        n = max(len(a), len(b))
        tot += n
        first = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                     min(len(a), len(b)) if len(a) != len(b) else None)
        if first is not None:
            div += n - first
    return div / max(tot, 1)


@pytest.mark.parametrize("page_size", [0, 8])
def test_engine_int8_greedy_divergence(page_size):
    cfg = dataclasses.replace(get_smoke_config("llama3.2-1b"),
                              attn_impl="flash", bcr_keep_frac=0.0)
    from repro.launch.serve import build_params
    params = build_params(cfg, log=lambda *a: None, decode_m=4)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(l)).astype(np.int32)
               for l in (7, 12, 5, 9)]
    outs = {}
    for name, kvd in (("fp", ""), ("q", "int8")):
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=4, capacity=64, page_size=page_size, seed=0,
            kv_dtype=kvd))
        outs[name] = eng.generate(prompts, max_new_tokens=12)
    assert _divergence(outs["fp"], outs["q"]) <= 0.25


def test_engine_kv_row_bytes_reflect_int8():
    cfg = dataclasses.replace(get_smoke_config("llama3.2-1b"),
                              attn_impl="flash", bcr_keep_frac=0.0)
    fns = model_fns(cfg)
    params = fns.init_params(jax.random.PRNGKey(0))
    rows = {}
    for name, kvd in (("fp", ""), ("q", "int8")):
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=2, capacity=32, page_size=8, kv_dtype=kvd))
        rows[name] = eng._kv_row_bytes
    assert rows["q"] < rows["fp"]
    # per layer per K/V: head_dim codes + one fp32 scale per kv head
    d, hkv = cfg.head_dim, cfg.num_kv_heads
    n_l = cfg.num_layers
    assert rows["q"] == n_l * 2 * hkv * (d + 4)
