"""Prefix-sharing paged KV: prefill-append kernel/ref equivalence, the
ref-counted / copy-on-write / LRU allocator lifecycle, and engine-level
equivalence — shared-prefix serving must produce exactly the tokens of the
unshared paged path (and of naive decode) while allocating strictly fewer
pages."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.kernels.paged_decode_attention import (
    paged_decode_attention, paged_prefill_append_attention)
from repro.kernels.ref import (paged_decode_attention_ref,
                               paged_prefill_append_ref)
from repro.models.api import model_fns
from repro.models.layers import dense_attention
from repro.serving import EngineConfig, InferenceEngine
from repro.serving.kv_slots import PagedSlotPool
from tests.test_serving import naive_greedy


# ---------------------------------------------------------------------------
# Kernel / ref math
# ---------------------------------------------------------------------------


def _append_case(totals, plens, page_size, hkv=2, g=2, d=16, seed=0):
    """Pages + tables whose gathered layout equals a contiguous history;
    suffix q rows sit at absolute positions plen + i."""
    rng = np.random.default_rng(seed)
    b = len(totals)
    s = max(t - p for t, p in zip(totals, plens))
    max_pages = max(-(-int(t) // page_size) for t in totals)
    n_pages = 1 + b * max_pages
    q = jnp.asarray(rng.normal(size=(b, s, hkv * g, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, hkv, page_size, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, hkv, page_size, d)),
                     jnp.float32)
    bt = np.zeros((b, max_pages), np.int32)
    pid = 1
    for i, t in enumerate(totals):
        for p in range(-(-int(t) // page_size)):
            bt[i, p] = pid
            pid += 1
    return (q, kp, vp, jnp.asarray(bt), jnp.asarray(plens, jnp.int32),
            jnp.asarray(totals, jnp.int32))


class TestPrefillAppendMath:
    @pytest.mark.parametrize("totals,plens,page_size", [
        ((13, 25, 8), (5, 16, 0), 8),    # partial pages + a cold (plen=0) row
        ((16, 32), (8, 24), 8),          # page-aligned prefixes
        ((21, 9), (17, 3), 4),           # suffix crosses page boundaries
    ])
    def test_ref_matches_dense_oracle(self, totals, plens, page_size):
        q, kp, vp, bt, pl, tl = _append_case(totals, plens, page_size)
        ref = paged_prefill_append_ref(q, kp, vp, bt, pl, tl)
        cap = bt.shape[1] * page_size
        # head-major pages -> contiguous (B, cap, Hkv, D) history
        kd = jnp.swapaxes(jnp.take(kp, bt, axis=0), 2, 3) \
            .reshape(len(totals), cap, 2, 16)
        vd = jnp.swapaxes(jnp.take(vp, bt, axis=0), 2, 3) \
            .reshape(len(totals), cap, 2, 16)
        for i, (t, p) in enumerate(zip(totals, plens)):
            sfx = t - p
            if sfx == 0:
                continue
            o = dense_attention(q[i:i + 1, :sfx], kd[i:i + 1, :t],
                                vd[i:i + 1, :t], causal=True, q_offset=p)
            np.testing.assert_allclose(np.asarray(ref[i, :sfx]),
                                       np.asarray(o[0]),
                                       atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("g,totals,plens,page_size", [
        # GQA ratios incl. MHA
        pytest.param(g, (13, 25, 8), (5, 16, 0), 8, id=str(g))
        for g in (1, 2, 3, 4)] + [
        # suffixes whose causal mask crosses a 128-position kernel step
        pytest.param(2, (150, 140, 40), (120, 131, 0), 8, id="step-8"),
        pytest.param(4, (300, 70), (250, 60), 32, id="step-32"),
    ])
    def test_kernel_matches_ref(self, g, totals, plens, page_size):
        # head dims of pipelined (16) and hand-copied (128) pages
        for d in (16, 128):
            q, kp, vp, bt, pl, tl = _append_case(totals, plens, page_size,
                                                 g=g, d=d)
            ref = paged_prefill_append_ref(q, kp, vp, bt, pl, tl)
            got = paged_prefill_append_attention(q, kp, vp, bt, pl, tl,
                                                 interpret=True)
            # rows past each slot's true suffix are garbage on both sides
            for i, (t, p) in enumerate(zip(totals, plens)):
                sfx = t - p
                np.testing.assert_allclose(np.asarray(got[i, :sfx]),
                                           np.asarray(ref[i, :sfx]),
                                           atol=1e-5, rtol=1e-5)

    def test_decode_is_the_s1_special_case(self):
        """The 1-row flash-decode is the S=1, plen=len-1 instance of the
        generalized kernel."""
        q, kp, vp, bt, pl, tl = _append_case((13, 25), (12, 24), 8)
        dec = paged_decode_attention(q[:, :1], kp, vp, bt, tl,
                                     interpret=True)
        app = paged_prefill_append_attention(q[:, :1], kp, vp, bt,
                                             tl - 1, tl, interpret=True)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(app),
                                   atol=1e-6)
        ref = paged_decode_attention_ref(q[:, :1], kp, vp, bt, tl)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Allocator: refcounts, prefix index, CoW, LRU
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def llama_fns():
    cfg = get_smoke_config("llama3.2-1b")
    fns = model_fns(cfg)
    params = fns.init_params(jax.random.PRNGKey(0))
    return cfg, fns, params


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 500, size=n).astype(np.int32)


class TestPrefixAllocator:
    PS = 8

    def _pool(self, fns, n_slots=3, capacity=64, n_pages=None):
        return PagedSlotPool(fns.init_cache, n_slots, capacity,
                             page_size=self.PS, n_pages=n_pages)

    def _admit_and_publish(self, pool, slot, prompt, total):
        hit = pool.admit_prefix(slot, prompt, total)
        assert hit is not None
        pool.ensure(slot, len(prompt))
        pool.lens[slot] = len(prompt)
        pool.register_prefix(slot, prompt)
        return hit

    def test_register_match_adopt_refcounts(self, llama_fns):
        cfg, fns, params = llama_fns
        pool = self._pool(fns)
        p = _prompt(20)                       # 2 full pages + partial
        assert self._admit_and_publish(pool, 0, p, 28) == 0   # cold
        # only FULL pages are registered; the partial page stays private
        hit, pages = pool.match_prefix(p)
        assert hit == 16 and len(pages) == 2
        # identical prompt: adoption bumps refcounts, suffix is [16, 20)
        hit2 = pool.admit_prefix(1, p, 28)
        assert hit2 == 16
        for pid in pages:
            assert pool._refcount[pid] == 2
        # retire the owner: shared pages survive for slot 1
        pool.release(0)
        for pid in pages:
            assert pool._refcount[pid] == 1
        pool.release(1)
        for pid in pages:
            assert pool._refcount[pid] == 0
            assert pid in pool._lru           # registered → LRU, not free

    def test_partial_tail_match_and_cow(self, llama_fns):
        """A shorter prompt that is a prefix of a cached longer one adopts
        the covering FULL page as its partial final page; the suffix write
        then forces a copy-on-write materialization."""
        cfg, fns, params = llama_fns
        pool = self._pool(fns)
        long = _prompt(24)                    # 3 full pages, all registered
        self._admit_and_publish(pool, 0, long, 32)
        short = long[:20]
        hit = pool.admit_prefix(1, short, 28)
        assert hit == 19                      # capped at L-1, mid-page
        shared_pid = int(pool.table[1, 2])
        assert shared_pid == int(pool.table[0, 2])   # the full page [16,24)
        assert pool._refcount[shared_pid] == 2
        # the suffix token at position 19 lands inside the shared page
        pair = pool.ensure_writable(1, 19)
        assert pair is not None and pair[0] == shared_pid
        assert pool._refcount[shared_pid] == 1       # dropped by slot 1
        assert int(pool.table[1, 2]) == pair[1] != shared_pid
        assert pool.stats["cow_copies"] == 1
        # private copy is writable in place now
        assert pool.ensure_writable(1, 19) is None

    def test_owner_write_into_registered_page_cows(self, llama_fns):
        """Registered pages are immutable even at refcount 1: a slot
        whose write frontier sits inside one (e.g. it adopted a full page
        as partial final page and everyone else retired) still copies."""
        cfg, fns, params = llama_fns
        pool = self._pool(fns)
        long = _prompt(16)                    # exactly 2 full pages
        self._admit_and_publish(pool, 0, long, 24)
        pool.release(0)
        short = long[:12]
        hit = pool.admit_prefix(1, short, 20)
        assert hit == 11                      # page [8,16) partially adopted
        pid = int(pool.table[1, 1])
        assert pool._refcount[pid] == 1       # sole owner, but registered
        pair = pool.ensure_writable(1, 11)
        assert pair is not None and pair[0] == pid

    def test_partial_adoption_reserves_cow_page(self, llama_fns):
        """Regression: the CoW copy of an adopted partial tail page is
        part of the slot's fresh-page demand — it must be reserved at
        admission, or free_pages() overstates and a later reservation
        over-commits the pool (allocator assert mid-serving)."""
        cfg, fns, params = llama_fns
        pool = self._pool(fns, n_slots=3, capacity=32, n_pages=6)  # 5 usable
        long = _prompt(16)
        self._admit_and_publish(pool, 0, long, 16)     # slot 0 active, 2 pp
        free_before = pool.free_pages()
        hit = pool.admit_prefix(1, long[:12], 16)      # partial-tail adopt
        assert hit == 11
        # pages_needed(16)=2, one full page kept → 1 fresh page (the CoW
        # copy) must be earmarked even though no boundary alloc is due
        assert pool._reserved[1] == 1
        assert pool.free_pages() == free_before - 1
        # a competitor can only claim what is genuinely left...
        assert not pool.reserve(2, 8 * pool.free_pages() + 1)
        # ...and slot 1's own CoW + ensure complete without exhaustion
        assert pool.ensure_writable(1, 11) is not None
        pool.ensure(1, 16)
        assert pool._reserved[1] == 0 and pool.free_pages() >= 0

    def test_lru_eviction_and_reclaim(self, llama_fns):
        cfg, fns, params = llama_fns
        pool = self._pool(fns, n_slots=2, capacity=32, n_pages=6)  # 5 usable
        a = _prompt(16, seed=1)
        self._admit_and_publish(pool, 0, a, 24)      # 3 pages (2 registered)
        pool.release(0)                              # 2 LRU + 1 free + 2 free
        # a hot prefix survives retirement: the next identical prompt
        # reclaims its pages from the LRU list (hit capped at L-1: the
        # last token is always recomputed to produce the sample logits)
        hit = pool.admit_prefix(0, a, 24)
        assert hit == 15 and pool.stats["evictions"] == 0
        pool.release(0)
        # demand exceeding free pages evicts LRU pages lazily
        assert pool.reserve(1, 32)                   # needs 4 of 5
        pool.ensure(1, 32)
        assert pool.stats["evictions"] >= 1
        # evicted prefix is gone from the index
        hit, pages = pool.match_prefix(a)
        assert hit < 16
        pool.release(1)

    def test_free_pages_scalar_counter_stays_consistent(self, llama_fns):
        """The micro-fix: free_pages() must track reserve/alloc/adopt/
        release without rescanning, never going negative, and always
        equal the recomputed ground truth."""
        cfg, fns, params = llama_fns
        pool = self._pool(fns, n_slots=3, capacity=32, n_pages=10)
        rng = np.random.default_rng(0)
        prompts = {s: _prompt(rng.integers(9, 25), seed=s) for s in range(3)}
        held = set()
        for step in range(200):
            truth = (len(pool._free) + len(pool._lru)
                     - int(pool._reserved.sum()))
            assert pool.free_pages() == truth
            assert pool.free_pages() >= 0
            slot = int(rng.integers(0, 3))
            if slot in held:
                if rng.random() < 0.5 and pool.lens[slot] < 30:
                    pool.ensure(slot, int(pool.lens[slot]) + 1)
                    pool.ensure_writable(slot, int(pool.lens[slot]))
                    pool.lens[slot] += 1
                else:
                    pool.register_prefix(slot, prompts[slot])
                    pool.release(slot)
                    held.discard(slot)
            else:
                p = prompts[slot]
                if pool.admit_prefix(slot, p, len(p) + 6) is not None:
                    pool.ensure(slot, len(p))
                    pool.lens[slot] = len(p)
                    held.add(slot)

    def test_reset_prefix_returns_lru_to_free(self, llama_fns):
        cfg, fns, params = llama_fns
        pool = self._pool(fns)
        p = _prompt(16)
        self._admit_and_publish(pool, 0, p, 24)
        pool.release(0)
        assert pool._lru
        before = len(pool._free)
        pool.reset_prefix()
        assert not pool._lru and not pool._page_key
        assert len(pool._free) == before + 2


# ---------------------------------------------------------------------------
# Engine equivalence: shared == unshared == naive
# ---------------------------------------------------------------------------


SYSTEM = np.arange(100, 119, dtype=np.int32)      # 19 tokens: partial page


def _requests(cfg, n=4, seed=5):
    """Shared-prefix workload: one system prompt + per-request user
    suffixes of mixed (page-misaligned) lengths."""
    rng = np.random.default_rng(seed)
    return [np.concatenate([SYSTEM, rng.integers(
        0, cfg.vocab_size, size=int(l)).astype(np.int32)])
        for l in (5, 9, 2, 7)[:n]]


class TestPrefixEngine:
    GEN = 6

    def _engine(self, cfg, params, shared, **kw):
        ec = EngineConfig(n_slots=2, capacity=64, page_size=8,
                          prefix_cache=shared, **kw)
        return InferenceEngine(cfg, params, ec)

    def test_shared_matches_unshared_and_naive_dense(self, llama_fns):
        cfg, fns, params = llama_fns
        prompts = _requests(cfg)
        ref = [naive_greedy(fns, params, p, self.GEN) for p in prompts]
        cold = self._engine(cfg, params, shared=False)
        got_cold = cold.generate(prompts, max_new_tokens=self.GEN)
        hot = self._engine(cfg, params, shared=True)
        got_hot = hot.generate(prompts, max_new_tokens=self.GEN)
        assert got_cold == ref
        assert got_hot == ref
        assert hot.stats["prefix_hit_tokens"] > 0
        assert hot.stats["pages_shared"] > 0
        # sharing allocates strictly fewer pages for the same workload
        assert (hot.stats["pages_allocated"]
                < cold.stats["pages_allocated"])

    def test_shared_matches_naive_packed(self, llama_fns):
        """Prefix sharing over BCR-packed weights: grouped projections +
        paged KV + suffix-only prefill, tokens unchanged."""
        from repro.launch.serve import pack_params
        cfg, fns, params = llama_fns
        cfg_p = dataclasses.replace(cfg, bcr_keep_frac=0.25,
                                    bcr_block=(16, 16))
        packed = pack_params(cfg_p, params)
        prompts = _requests(cfg)[:3]
        ref = [naive_greedy(fns, packed, p, self.GEN) for p in prompts]
        eng = self._engine(cfg_p, packed, shared=True)
        got = eng.generate(prompts, max_new_tokens=self.GEN)
        assert got == ref
        assert eng.stats["prefix_hit_tokens"] > 0

    def test_shared_with_append_kernel_impl(self, llama_fns):
        """attn_impl="paged_interpret" routes both decode AND the suffix
        prefill through the Pallas kernels — tokens unchanged."""
        cfg, fns, params = llama_fns
        cfg_k = dataclasses.replace(cfg, attn_impl="paged_interpret")
        prompts = _requests(cfg)[:2]
        ref = [naive_greedy(fns, params, p, 4) for p in prompts]
        eng = self._engine(cfg_k, params, shared=True)
        [got0] = eng.generate([prompts[0]], max_new_tokens=4)
        [got1] = eng.generate([prompts[1]], max_new_tokens=4)
        assert [got0, got1] == ref
        assert eng.stats["prefix_hit_tokens"] > 0

    def test_full_prompt_hit_cow(self, llama_fns):
        """A prompt that is a strict prefix of a cached longer one hits up
        to L-1 tokens via the partial-tail match; its 1-token suffix lands
        mid-page in a shared page → copy-on-write at admission, tokens
        still exact."""
        cfg, fns, params = llama_fns
        long = np.arange(200, 224, dtype=np.int32)     # 3 full pages
        short = long[:20]
        ref = [naive_greedy(fns, params, p, 4) for p in (long, short)]
        eng = self._engine(cfg, params, shared=True)
        [got_long] = eng.generate([long], max_new_tokens=4)
        [got_short] = eng.generate([short], max_new_tokens=4)
        assert got_long == ref[0]
        assert got_short == ref[1]
        assert eng.stats["cow_copies"] >= 1
        assert eng.stats["prefix_hit_tokens"] == 19    # capped at L-1

    def test_staggered_sharing_while_owner_decodes(self, llama_fns):
        """A second identical prompt admitted while the first is still
        decoding shares its pages live (refcount 2); both token streams
        match naive."""
        cfg, fns, params = llama_fns
        prompts = _requests(cfg)[:2]
        ref = [naive_greedy(fns, params, p, self.GEN) for p in prompts]
        eng = self._engine(cfg, params, shared=True)
        ra = eng.submit(prompts[0], max_new_tokens=self.GEN)
        for _ in range(2):
            eng.step()
        rb = eng.submit(prompts[1], max_new_tokens=self.GEN)
        done = {r.rid: r for r in eng.run()}
        assert done[ra].generated == ref[0]
        assert done[rb].generated == ref[1]
        assert eng.stats["prefix_hit_tokens"] > 0

    def test_oversubscribed_fcfs_no_queue_jumping(self, llama_fns):
        """Strict FCFS under page pressure: a later prefix-hit request
        that WOULD fit the leftover budget must not jump an earlier
        stalled cold request, and everything still completes correctly."""
        cfg, fns, params = llama_fns
        sys_p = _requests(cfg)[0]
        fat = np.random.default_rng(9).integers(
            0, cfg.vocab_size, size=40).astype(np.int32)   # page-hungry
        ref = {p.tobytes(): naive_greedy(fns, params, p, 4)
               for p in (sys_p, fat)}
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=3, capacity=64, page_size=8, kv_pages=9,
            prefix_cache=True))
        r0 = eng.submit(sys_p, max_new_tokens=4)        # seeds the cache
        eng.step()
        r1 = eng.submit(fat, max_new_tokens=4)          # stalls on pages
        r2 = eng.submit(sys_p, max_new_tokens=4)        # hit, would fit
        order = []
        while eng.sched.has_work():
            for r in eng.step():
                order.append(r.rid)
        assert eng.stats["page_stalls"] > 0
        done = {r.rid: r for r in eng.sched.finished}
        assert done[r1].generated == ref[fat.tobytes()]
        assert done[r2].generated == ref[sys_p.tobytes()]
        # FCFS: the fat request was never overtaken at admission
        assert done[r1].admit_time <= done[r2].admit_time

    def test_recurrent_family_prefix_cache_noop(self):
        cfg = get_smoke_config("rwkv6-3b")
        fns = model_fns(cfg)
        params = fns.init_params(jax.random.PRNGKey(0))
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=2, capacity=32, page_size=8, prefix_cache=True))
        assert not eng.prefix_cache        # no pages to share
