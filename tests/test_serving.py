"""Continuous-batching serving stack: scheduler unit tests, slot-pool
invariants, and end-to-end engine equivalence (engine greedy tokens ==
naive single-request decode) for dense and BCR-packed params."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.models.api import model_fns
from repro.serving import EngineConfig, InferenceEngine, Request, Scheduler
from repro.serving.kv_slots import SlotPool, cache_batch_axes, seat_prefill


def _req(p=4, **kw):
    return Request(prompt=np.zeros(p, np.int32), **kw)


class TestScheduler:
    def test_fcfs_admission_order(self):
        s = Scheduler(n_slots=2)
        r = [s.submit(_req()) for _ in range(4)]
        admitted = s.admit()
        assert [q.rid for q, _ in admitted] == r[:2]
        assert s.free_slots() == 0 and len(s.waiting) == 2

    def test_slot_reuse_after_retire(self):
        s = Scheduler(n_slots=2)
        for _ in range(3):
            s.submit(_req())
        (r0, s0), (r1, s1) = s.admit()
        s.retire(s0)
        [(r2, s2)] = s.admit()
        assert s2 == s0                      # freed slot is reused
        assert r2.rid > r1.rid               # and FCFS order holds

    def test_retirement_order_recorded(self):
        s = Scheduler(n_slots=3)
        for _ in range(3):
            s.submit(_req())
        pairs = s.admit()
        # retire out of admission order; finished list preserves retire order
        s.retire(pairs[2][1])
        s.retire(pairs[0][1])
        s.retire(pairs[1][1])
        assert [r.rid for r in s.finished] == [pairs[2][0].rid,
                                               pairs[0][0].rid,
                                               pairs[1][0].rid]
        assert not s.has_work()

    def test_max_admit_bounds_prefill_burst(self):
        s = Scheduler(n_slots=4)
        for _ in range(4):
            s.submit(_req())
        assert len(s.admit(max_admit=1)) == 1
        assert len(s.admit()) == 3

    def test_request_finish_conditions(self):
        r = _req(max_new_tokens=2)
        r.generated = [5]
        assert not r.is_finished()
        r.generated = [5, 6]
        assert r.is_finished()
        r2 = _req(max_new_tokens=8, eos_id=7)
        r2.generated = [3, 7]
        assert r2.is_finished()


class TestSlotPool:
    def test_batch_axes_discovered_per_layout(self):
        # llama: unstacked prefix (batch axis 0) absent, scanned stack
        # leaves carry batch at axis 1
        fns = model_fns(get_smoke_config("llama3.2-1b"))
        axes = cache_batch_axes(fns.init_cache)
        for ax in jax.tree_util.tree_leaves(axes):
            assert ax == 1          # stack leaves: (repeats, batch, ...)

    def test_insert_and_release(self):
        fns = model_fns(get_smoke_config("llama3.2-1b"))
        pool = SlotPool(fns.init_cache, n_slots=3, capacity=16)
        params = fns.init_params(jax.random.PRNGKey(0))
        toks = jnp.zeros((1, 4), jnp.int32)
        _, pcache = fns.prefill(params, {"tokens": toks})
        pool.insert(pcache, slot=1, length=4)
        assert list(pool.lens) == [0, 4, 0]
        pool.advance(1)
        assert pool.lens[1] == 5
        pool.release(1)
        assert pool.lens[1] == 0

    def test_insert_rejects_overflow(self):
        fns = model_fns(get_smoke_config("llama3.2-1b"))
        pool = SlotPool(fns.init_cache, n_slots=1, capacity=4)
        with pytest.raises(AssertionError):
            pool.insert({}, slot=0, length=8)


@pytest.fixture(scope="module")
def llama():
    cfg = dataclasses.replace(get_smoke_config("llama3.2-1b"),
                              bcr_keep_frac=0.25, bcr_block=(16, 16))
    fns = model_fns(cfg)
    params = fns.init_params(jax.random.PRNGKey(0))
    return cfg, fns, params


def naive_greedy(fns, params, prompt, gen, capacity=64):
    """Reference: exact-length batch-1 prefill + step-by-step greedy."""
    logits, pcache = fns.prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    cache = seat_prefill(fns.init_cache, pcache, 1, capacity)
    lens = jnp.asarray([len(prompt)], jnp.int32)
    out = [int(jnp.argmax(logits[0, -1]))]
    for i in range(gen - 1):
        batch = {"tokens": jnp.asarray([[out[-1]]], jnp.int32),
                 "cache_len": lens + i}
        logits, cache = fns.decode_step(params, batch, cache)
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


class TestEngineEquivalence:
    PROMPT_LENS = (5, 16, 9, 12)
    GEN = 8

    def _prompts(self, cfg):
        rng = np.random.default_rng(42)
        return [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32)
                for p in self.PROMPT_LENS]

    def test_engine_matches_naive_dense(self, llama):
        cfg, fns, params = llama
        prompts = self._prompts(cfg)
        ref = [naive_greedy(fns, params, p, self.GEN) for p in prompts]
        # fewer slots than requests → slot reuse + mixed-age decode batches
        eng = InferenceEngine(cfg, params, EngineConfig(n_slots=2, capacity=64))
        got = eng.generate(prompts, max_new_tokens=self.GEN)
        assert got == ref
        occ = eng.stats["slot_occupancy"]
        assert max(occ) == 2     # the batch really was shared

    def test_engine_matches_naive_packed(self, llama):
        from repro.launch.serve import pack_params
        cfg, fns, params = llama
        packed = pack_params(cfg, params)
        prompts = self._prompts(cfg)
        ref = [naive_greedy(fns, packed, p, self.GEN) for p in prompts]
        eng = InferenceEngine(cfg, packed, EngineConfig(n_slots=2, capacity=64))
        got = eng.generate(prompts, max_new_tokens=self.GEN)
        assert got == ref

    def test_mixed_age_batch_via_staggered_submission(self, llama):
        """Admission mid-flight: request B joins while A is decoding; both
        still reproduce the naive tokens."""
        cfg, fns, params = llama
        prompts = self._prompts(cfg)[:2]
        ref = [naive_greedy(fns, params, p, self.GEN) for p in prompts]
        eng = InferenceEngine(cfg, params, EngineConfig(n_slots=2, capacity=64))
        ra = eng.submit(prompts[0], max_new_tokens=self.GEN)
        for _ in range(3):                    # A decodes alone for 3 steps
            eng.step()
        rb = eng.submit(prompts[1], max_new_tokens=self.GEN)
        done = {r.rid: r for r in eng.run()}
        assert done[ra].generated == ref[0]
        assert done[rb].generated == ref[1]

    def test_eos_early_stop(self, llama):
        cfg, fns, params = llama
        prompt = self._prompts(cfg)[0]
        ref = naive_greedy(fns, params, prompt, self.GEN)
        eos = ref[2]
        eng = InferenceEngine(cfg, params, EngineConfig(n_slots=2, capacity=64))
        [got] = eng.generate([prompt], max_new_tokens=self.GEN, eos_id=eos)
        assert got == ref[:3]

    def test_sampling_valid_and_reproducible(self, llama):
        cfg, fns, params = llama
        prompts = self._prompts(cfg)[:2]
        outs = []
        for _ in range(2):
            eng = InferenceEngine(cfg, params,
                                  EngineConfig(n_slots=2, capacity=64, seed=7))
            outs.append(eng.generate(prompts, max_new_tokens=4,
                                     temperature=0.9, top_k=8))
        assert outs[0] == outs[1]            # same seed → same samples
        assert all(0 <= t < cfg.vocab_size
                   for row in outs[0] for t in row)

    def test_capacity_guard(self, llama):
        # oversized requests come back as a REJECTED rid instead of a
        # ValueError that would kill an open-loop driver
        cfg, fns, params = llama
        eng = InferenceEngine(cfg, params, EngineConfig(n_slots=1, capacity=8))
        rid = eng.submit(np.zeros(6, np.int32), max_new_tokens=4)
        rej = eng.sched.finished[-1]
        assert rej.rid == rid and rej.status == "REJECTED"
        assert "capacity" in rej.error
        # the engine still serves later, well-sized requests
        out = eng.generate([np.zeros(4, np.int32)], max_new_tokens=4)
        assert len(out[0]) == 4

    def test_encdec_rejected(self):
        cfg = get_smoke_config("whisper-large-v3")
        with pytest.raises(NotImplementedError):
            InferenceEngine(cfg, params=None, ec=EngineConfig())

    def test_moe_served(self):
        # the mask-aware router excludes garbage rows from expert
        # capacity, so MoE families construct (full equivalence in
        # TestMoEEngine)
        cfg = get_smoke_config("deepseek-moe-16b")
        fns = model_fns(cfg)
        params = fns.init_params(jax.random.PRNGKey(0))
        eng = InferenceEngine(cfg, params, EngineConfig(n_slots=2,
                                                        capacity=32))
        assert eng.cfg.num_experts > 0


class TestChunkedBackfill:
    """Steady-state admission batching: retirements free slots one at a
    time; the engine defers briefly and runs ONE merged prefill for the
    backfill instead of a single-row dispatch per retirement."""

    def _run(self, llama, chunk, n=6, slots=2):
        cfg, fns, params = llama
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32)
                   for p in (5, 16, 9, 12, 7, 20)[:n]]
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=slots, capacity=64, backfill_chunk=chunk,
            backfill_max_defer=2))
        got = eng.generate(prompts, max_new_tokens=6)
        return eng, got

    def test_merged_backfill_fewer_dispatches_same_tokens(self, llama):
        eng1, got1 = self._run(llama, chunk=1)   # admit eagerly, per slot
        eng2, got2 = self._run(llama, chunk=2)   # chunked backfill
        assert got1 == got2                      # admission timing is
        # invisible to per-request greedy tokens
        assert eng2.stats["prefills"] <= eng1.stats["prefills"]
        # every admission still ran exactly one prefill row
        assert eng1.stats["prefill_rows"] >= len(got1)
        assert eng2.stats["prefill_rows"] >= len(got2)

    def test_mixed_buckets_share_one_dispatch(self, llama):
        """Admissions in the same step merge across prompt-length buckets
        into one padded prefill program."""
        cfg, fns, params = llama
        rng = np.random.default_rng(1)
        # lengths 5 and 16 land in different power-of-two buckets
        prompts = [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32)
                   for p in (5, 16)]
        ref = [naive_greedy(fns, params, p, 4) for p in prompts]
        eng = InferenceEngine(cfg, params, EngineConfig(n_slots=2,
                                                        capacity=64))
        got = eng.generate(prompts, max_new_tokens=4)
        assert got == ref
        assert eng.stats["prefills"] == 1        # one merged dispatch


class TestMoEEngine:
    """Mask-aware MoE routing in the engine: free-slot garbage rows and
    admission pad rows/positions no longer consume expert capacity, so
    ragged continuous-batching decode reproduces naive single-request
    decode. capacity_factor is raised so no REAL token is ever dropped —
    with drops, token ranks inside a shared dispatch group differ between
    batch compositions by construction, which is a property of
    capacity-factor MoE, not of the engine."""

    def _cfg(self):
        return dataclasses.replace(get_smoke_config("deepseek-moe-16b"),
                                   capacity_factor=8.0)

    def test_engine_matches_naive(self):
        cfg = self._cfg()
        fns = model_fns(cfg)
        params = fns.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32)
                   for p in (5, 9, 7)]
        ref = [naive_greedy(fns, params, p, 6, capacity=32) for p in prompts]
        eng = InferenceEngine(cfg, params,
                              EngineConfig(n_slots=2, capacity=32))
        got = eng.generate(prompts, max_new_tokens=6)
        assert got == ref

    def test_masked_rows_do_not_shift_capacity(self):
        """moe_apply unit check: adding masked garbage rows leaves the
        real rows' outputs bit-identical."""
        from repro.models.moe import moe_apply
        cfg = self._cfg()
        from repro.models.moe import moe_init
        params = moe_init(jax.random.PRNGKey(1), cfg)
        rng = np.random.default_rng(3)
        real = jnp.asarray(rng.normal(size=(2, 4, cfg.d_model)), jnp.float32)
        junk = jnp.asarray(rng.normal(size=(2, 4, cfg.d_model)) * 50,
                           jnp.float32)
        x = jnp.concatenate([real, junk], axis=0)
        mask = jnp.asarray([[True] * 4, [True] * 4,
                            [False] * 4, [False] * 4])
        y_masked = moe_apply(params, x, cfg, token_mask=mask)
        y_alone = moe_apply(params, real, cfg,
                            token_mask=jnp.ones((2, 4), bool))
        np.testing.assert_allclose(np.asarray(y_masked[:2]),
                                   np.asarray(y_alone), atol=1e-5,
                                   rtol=1e-5)


class TestRecurrentFamilies:
    @pytest.mark.parametrize("arch", ["rwkv6-3b"])
    def test_engine_matches_naive(self, arch):
        cfg = get_smoke_config(arch)
        fns = model_fns(cfg)
        params = fns.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32)
                   for p in (5, 9)]
        ref = [naive_greedy(fns, params, p, 5) for p in prompts]
        eng = InferenceEngine(cfg, params, EngineConfig(n_slots=2, capacity=32))
        assert not eng.pad_prefill   # recurrent state: exact-length prefill
        got = eng.generate(prompts, max_new_tokens=5)
        assert got == ref


class TestSelectBackend:
    """The serving CLIs' kernel backend comes from the platform; an
    explicit ``--impl`` overrides it and impossible pairs are refused."""

    @pytest.mark.parametrize("platform,impl,page_size,want", [
        ("cpu", None, 16, ("ref", None)),
        ("cpu", "interpret", 16, ("interpret", "paged_interpret")),
        ("cpu", "interpret", 0, ("interpret", None)),
        ("tpu", None, 16, ("pallas", "paged")),
        ("tpu", None, 0, ("pallas", None)),
        ("tpu", "ref", 16, ("ref", None)),
    ])
    def test_choice(self, monkeypatch, platform, impl, page_size, want):
        from repro.launch.serve import select_backend
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert select_backend(impl, page_size) == want

    @pytest.mark.parametrize("platform,impl", [("tpu", "interpret"),
                                               ("cpu", "pallas")])
    def test_refused(self, monkeypatch, platform, impl):
        from repro.launch.serve import select_backend
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        with pytest.raises(ValueError):
            select_backend(impl, 16)
