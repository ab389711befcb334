"""Block-paged KV decode: kernel/ref equivalence vs masked-dense attention,
page-allocator lifecycle, and engine-vs-naive generation with paging on."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.kernels.paged_decode_attention import (
    attention_blocks, attention_grid_steps, paged_decode_attention,
    paged_kv_bytes)
from repro.kernels.ref import paged_decode_attention_ref
from repro.models.api import model_fns
from repro.models.layers import decode_attention
from repro.serving import EngineConfig, InferenceEngine
from repro.serving.kv_slots import PagedSlotPool
from tests.test_serving import naive_greedy


#: head dims the kernel tests run at: pages of a narrow D are pipelined
#: blocks, those of a 128-lane D are copied by hand
HEAD_DIMS = (16, 128)


def _paged_case(lens, page_size, hkv=2, g=2, d=16, n_cols=None, seed=0):
    """Pages + tables whose gathered layout equals a contiguous cache, so
    the masked-dense path is an oracle for the paged ones."""
    rng = np.random.default_rng(seed)
    b = len(lens)
    max_pages = n_cols or max(
        -(-int(l) // page_size) for l in lens) or 1
    n_pages = 1 + b * max_pages
    q = jnp.asarray(rng.normal(size=(b, 1, hkv * g, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, hkv, page_size, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, hkv, page_size, d)),
                     jnp.float32)
    bt = np.zeros((b, max_pages), np.int32)
    pid = 1
    for i, l in enumerate(lens):
        for p in range(-(-int(l) // page_size)):
            bt[i, p] = pid
            pid += 1
    lens = jnp.asarray(lens, jnp.int32)
    bt = jnp.asarray(bt)
    cap = max_pages * page_size
    # head-major pages (B, cols, Hkv, ps, D) -> contiguous (B, cap, Hkv, D)
    k_dense = jnp.swapaxes(jnp.take(kp, bt, axis=0), 2, 3) \
        .reshape(b, cap, hkv, d)
    v_dense = jnp.swapaxes(jnp.take(vp, bt, axis=0), 2, 3) \
        .reshape(b, cap, hkv, d)
    return q, kp, vp, bt, lens, k_dense, v_dense


class TestPagedAttentionMath:
    @pytest.mark.parametrize("lens,page_size", [
        ((13, 8, 25, 1), 8),       # partial final pages + a 1-token slot
        ((16, 32), 16),            # exact page fills
        ((5,), 8),                 # single slot, single partial page
        ((7, 64, 33), 32),         # mixed ages, larger pages
    ])
    def test_ref_matches_masked_dense(self, lens, page_size):
        q, kp, vp, bt, lv, kd, vd = _paged_case(lens, page_size)
        ref = paged_decode_attention_ref(q, kp, vp, bt, lv)
        dense = decode_attention(q, kd, vd, lv)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("g", [1, 2, 4, 8])  # GQA ratios incl. MHA
    def test_gqa_ratios(self, g):
        for d in HEAD_DIMS:
            # the 200-token slot spans two 16-page kernel steps
            q, kp, vp, bt, lv, kd, vd = _paged_case((9, 17, 200), 8, hkv=2,
                                                    g=g, d=d)
            ref = paged_decode_attention_ref(q, kp, vp, bt, lv)
            dense = decode_attention(q, kd, vd, lv)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(dense),
                                       atol=1e-5, rtol=1e-5)
            got = paged_decode_attention(q, kp, vp, bt, lv, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("lens,page_size", [
        ((13, 8, 25, 1), 8),
        ((16, 32), 16),
        ((5, 0, 12), 8),           # dead slot rides along in the grid
        # 16 pages a step: 38 and 17 live pages end mid-step; a dead slot
        ((300, 5, 0, 129), 8),
        ((0, 200, 0, 33), 8),      # leading dead slots, then live ones
        ((7, 64, 33), 32),         # table of 3 columns, under 4 a step
        ((100, 150, 0), 32),       # table of 5 columns, 4 a step
    ])
    def test_kernel_matches_ref(self, lens, page_size):
        for d in HEAD_DIMS:
            q, kp, vp, bt, lv, _, _ = _paged_case(lens, page_size, d=d)
            ref = paged_decode_attention_ref(q, kp, vp, bt, lv)
            got = paged_decode_attention(q, kp, vp, bt, lv, interpret=True)
            live = np.asarray(lv) > 0      # dead-slot rows are garbage
            np.testing.assert_allclose(np.asarray(got)[live],
                                       np.asarray(ref)[live],
                                       atol=1e-5, rtol=1e-5)

    def test_kernel_gqa_group_padding(self):
        # H=12 over Hkv=4 → G=3, padded to the sublane granule inside
        q, kp, vp, bt, lv, kd, vd = _paged_case((11, 20), 8, hkv=4, g=3)
        got = paged_decode_attention(q, kp, vp, bt, lv, interpret=True)
        dense = decode_attention(q, kd, vd, lv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)

    def test_narrow_table_ignores_dead_columns(self):
        """A table truncated to the live bucket gives identical output —
        the contract that lets the engine hand over only live columns."""
        q, kp, vp, bt, lv, _, _ = _paged_case((5, 9), 8, n_cols=6)
        wide = paged_decode_attention_ref(q, kp, vp, bt, lv)
        narrow = paged_decode_attention_ref(q, kp, vp, bt[:, :2], lv)
        np.testing.assert_allclose(np.asarray(wide), np.asarray(narrow),
                                   atol=1e-6)

    @pytest.mark.parametrize("lens,page_size,hkv,rows,n_cols", [
        ((300, 5, 0, 129), 8, 2, 2, 38),
        ((100, 150, 0), 32, 2, 2, 5),
        ((7,), 16, 8, 4, 1),
        ((2000, 1, 700), 16, 8, 4 * 512, 128),   # long append: head groups
    ])
    def test_grid_step_counts(self, lens, page_size, hkv, rows, n_cols):
        """``attention_grid_steps`` against a walk of the kernel's grid:
        a step is live when one of its table columns is a live page."""
        pages, heads = attention_blocks(page_size, hkv, 16, 2, rows, n_cols)
        grid = live = 0
        for n in lens:
            for _ in range(hkv // heads):
                for j in range(-(-n_cols // pages)):
                    grid += 1
                    live += j * pages * page_size < n
        assert attention_grid_steps(np.asarray(lens), page_size, hkv, 16, 2,
                                    rows, n_cols) == (grid, live)

    @pytest.mark.parametrize("args,blocks", [
        ((16, 8, 128, 2, 4, 128), (8, 8)),     # decode: 128 lanes, all heads
        ((16, 8, 128, 2, 4, 4), (4, 8)),       # capped by the table
        ((16, 20, 128, 2, 1, 128), (8, 20)),   # MHA
        ((16, 8, 128, 2, 4 * 64, 128), (8, 4)),    # append: head groups
        ((16, 8, 128, 2, 4 * 512, 128), (8, 1)),   # long append suffix
        ((256, 2, 64, 1, 8, 16), (1, 2)),      # pages wider than 128 lanes
    ])
    def test_block_rule(self, args, blocks):
        assert attention_blocks(*args) == blocks

    def test_kv_bytes_scale_with_live_tokens(self):
        few = paged_kv_bytes(np.asarray([3, 3]), 8, 2, 16)
        many = paged_kv_bytes(np.asarray([300, 300]), 8, 2, 16)
        assert many > 30 * few        # live pages, not provisioned width


@pytest.fixture(scope="module")
def llama_fns():
    cfg = get_smoke_config("llama3.2-1b")
    fns = model_fns(cfg)
    params = fns.init_params(jax.random.PRNGKey(0))
    return cfg, fns, params


class TestPageAllocator:
    def _pool(self, fns, n_slots=2, capacity=32, page_size=8, n_pages=None):
        return PagedSlotPool(fns.init_cache, n_slots, capacity,
                             page_size=page_size, n_pages=n_pages)

    def test_reserve_alloc_release_reuse(self, llama_fns):
        cfg, fns, params = llama_fns
        pool = self._pool(fns, n_pages=5)        # 4 allocatable + null
        assert pool.free_pages() == 4
        assert pool.reserve(0, 17)               # 3 pages of 8
        assert pool.free_pages() == 1
        pool.ensure(0, 9)                        # 2 pages materialize
        first_pages = set(pool.table[0, :2])
        assert 0 not in first_pages
        pool.ensure(0, 17)                       # third from the budget
        assert pool.free_pages() == 1
        assert not pool.reserve(1, 17)           # over budget → refused
        pool.release(0)
        assert pool.free_pages() == 4
        assert set(pool.table[0]) == {0}         # table row wiped
        assert pool.reserve(1, 17)
        pool.ensure(1, 17)
        assert set(pool.table[1, :3]) <= first_pages | {3, 4}  # reused ids

    def test_ensure_is_lazy(self, llama_fns):
        cfg, fns, params = llama_fns
        pool = self._pool(fns)
        assert pool.reserve(0, 32)               # 4-page worst case
        pool.ensure(0, 3)
        assert pool._n_alloc[0] == 1             # only the prompt page
        pool.ensure(0, 8)
        assert pool._n_alloc[0] == 1             # same page still covers
        pool.ensure(0, 9)                        # boundary crossing
        assert pool._n_alloc[0] == 2

    def test_table_width_buckets_to_pow2(self, llama_fns):
        cfg, fns, params = llama_fns
        pool = self._pool(fns, capacity=64)
        assert pool.table_width() == 1           # idle pool
        pool.reserve(0, 64)
        pool.ensure(0, 17)
        pool.lens[0] = 17                        # needs 3 pages → bucket 4
        assert pool.table_width() == 4

    def test_prefill_rows_land_in_table_pages(self, llama_fns):
        cfg, fns, params = llama_fns
        pool = self._pool(fns, n_slots=2, capacity=32, page_size=8)
        toks = jnp.zeros((1, 8), jnp.int32)
        _, pcache = fns.prefill(params, {"tokens": toks})
        assert pool.reserve(1, 8)
        pool.insert(pcache, slot=1, length=8)
        assert pool.lens[1] == 8 and pool._n_alloc[1] == 1
        pid = int(pool.table[1, 0])
        # the slot's page now holds the prefill K rows (stack leaf layout:
        # (repeats, n_pages, Hkv, page_size, D); prefill leaf (repeats,
        # batch, seq, Hkv, D))
        leaf = jax.tree_util.tree_leaves(pool.cache)[0]
        src = jax.tree_util.tree_leaves(pcache)[0]
        np.testing.assert_allclose(np.asarray(leaf[:, pid]),
                                   np.asarray(jnp.swapaxes(src[:, 0], 1, 2)),
                                   atol=1e-6)


class TestPagedEngine:
    PROMPT_LENS = (5, 16, 9, 12)
    GEN = 8

    def _prompts(self, cfg):
        rng = np.random.default_rng(42)
        return [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32)
                for p in self.PROMPT_LENS]

    def test_engine_matches_naive_dense(self, llama_fns):
        cfg, fns, params = llama_fns
        prompts = self._prompts(cfg)
        ref = [naive_greedy(fns, params, p, self.GEN) for p in prompts]
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=2, capacity=64, page_size=8))
        got = eng.generate(prompts, max_new_tokens=self.GEN)
        assert got == ref
        assert eng.paged
        # bytes accounting scaled with live tokens, not capacity
        steps = eng.stats["decode_steps"]
        assert 0 < eng.stats["kv_bytes_read_live"] \
            <= eng.stats["kv_bytes_read"]

    def test_engine_matches_naive_packed(self, llama_fns):
        """Paged decode over BCR-packed weights — the full serving stack
        (grouped projections + fused epilogue + paged KV) vs naive."""
        from repro.launch.serve import pack_params
        cfg, fns, params = llama_fns
        cfg_p = dataclasses.replace(cfg, bcr_keep_frac=0.25,
                                    bcr_block=(16, 16))
        packed = pack_params(cfg_p, params)
        prompts = self._prompts(cfg)
        ref = [naive_greedy(fns, packed, p, self.GEN) for p in prompts]
        eng = InferenceEngine(cfg_p, packed, EngineConfig(
            n_slots=2, capacity=64, page_size=8))
        got = eng.generate(prompts, max_new_tokens=self.GEN)
        assert got == ref

    def test_engine_paged_kernel_impl(self, llama_fns):
        """cfg.attn_impl="paged_interpret" routes decode through the Pallas
        flash-decode kernel (interpret mode on CPU) — tokens unchanged."""
        cfg, fns, params = llama_fns
        cfg_k = dataclasses.replace(cfg, attn_impl="paged_interpret")
        prompts = self._prompts(cfg)[:2]
        ref = [naive_greedy(fns, params, p, 4) for p in prompts]
        eng = InferenceEngine(cfg_k, params, EngineConfig(
            n_slots=2, capacity=32, page_size=8))
        got = eng.generate(prompts, max_new_tokens=4)
        assert got == ref

    def test_oversubscribed_pool_stalls_then_completes(self, llama_fns):
        """kv_pages below worst-case demand: admission control defers
        requests instead of corrupting running ones; output unchanged."""
        cfg, fns, params = llama_fns
        prompts = self._prompts(cfg)
        ref = [naive_greedy(fns, params, p, self.GEN) for p in prompts]
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=2, capacity=64, page_size=8, kv_pages=5))
        got = eng.generate(prompts, max_new_tokens=self.GEN)
        assert got == ref
        assert eng.stats["page_stalls"] > 0

    def test_submit_rejects_request_larger_than_pool(self, llama_fns):
        cfg, fns, params = llama_fns
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=1, capacity=64, page_size=8, kv_pages=3))
        rid = eng.submit(np.zeros(20, np.int32), max_new_tokens=8)
        rej = eng.sched.finished[-1]
        assert rej.rid == rid and rej.status == "REJECTED"
        assert "pages" in rej.error

    def test_recurrent_family_keeps_unpaged_path(self):
        cfg = get_smoke_config("rwkv6-3b")
        fns = model_fns(cfg)
        params = fns.init_params(jax.random.PRNGKey(0))
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=2, capacity=32, page_size=8))
        assert not eng.paged               # no attention K/V to page
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32)
                   for p in (5, 9)]
        ref = [naive_greedy(fns, params, p, 4) for p in prompts]
        assert eng.generate(prompts, max_new_tokens=4) == ref
