"""Substrate tests: optimizer, data, checkpointing, fault tolerance,
gradient compression, HLO cost accounting, analytic param model."""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.optim import adamw
from repro.optim.grad_compress import (compressed_bytes, dequantize_int8,
                                       ef_compress, ef_init, quantize_int8,
                                       topk_sparsify)


class TestAdamW:
    def test_minimizes_quadratic(self):
        cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                                total_steps=200, grad_clip=0)
        target = jnp.asarray([1.0, -2.0, 3.0])
        params = {"w": jnp.zeros(3)}
        state = adamw.init(params)
        for _ in range(150):
            g = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
            params, state, _ = adamw.update(cfg, g, state, params)
        np.testing.assert_allclose(np.asarray(params["w"]), target, atol=0.1)

    def test_schedule_shape(self):
        cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                                min_lr_frac=0.1)
        assert float(adamw.schedule(cfg, jnp.asarray(0))) == 0.0
        assert float(adamw.schedule(cfg, jnp.asarray(10))) == pytest.approx(1.0)
        assert float(adamw.schedule(cfg, jnp.asarray(100))) == pytest.approx(0.1, rel=1e-2)

    def test_grad_clipping(self):
        g = {"w": jnp.full((4,), 100.0)}
        clipped, norm = adamw.clip_by_global_norm(g, 1.0)
        assert float(adamw.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
        assert float(norm) == pytest.approx(200.0)


class TestGradCompress:
    def test_int8_roundtrip_small_error(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (256,))
        q, s = quantize_int8(x)
        err = jnp.abs(dequantize_int8(q, s) - x).max()
        assert float(err) <= float(s) * 0.51

    def test_topk_keeps_largest(self):
        x = jnp.asarray([0.1, -5.0, 0.2, 3.0])
        y = topk_sparsify(x, 0.5)
        np.testing.assert_allclose(np.asarray(y), [0.0, -5.0, 0.0, 3.0])

    def test_error_feedback_accumulates(self):
        """EF: repeated compression of a constant gradient must pass the
        full magnitude through over time (no systematic bias)."""
        g = {"w": jnp.full((64,), 0.01)}
        st = ef_init(g)
        total = jnp.zeros((64,))
        for _ in range(20):
            out, st = ef_compress(g, st, codec="topk", topk_frac=0.1)
            total = total + out["w"]
        # average transmitted ≈ average true gradient
        np.testing.assert_allclose(float(total.mean()) / 20, 0.01, rtol=0.3)

    def test_wire_bytes(self):
        g = {"w": jnp.zeros((1000,))}
        assert compressed_bytes(g, "int8") == 1000
        assert compressed_bytes(g, "topk", 0.05) == 50 * 8


class TestCheckpoint:
    def test_roundtrip_and_gc(self, tmp_path):
        from repro.checkpoint.checkpointing import CheckpointManager
        mgr = CheckpointManager(str(tmp_path), keep=2)
        tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": {"c": jnp.asarray(3, jnp.int32)}}
        for step in (1, 2, 3):
            mgr.save(step, tree)
        assert mgr.all_steps() == [2, 3]          # gc keeps last 2
        restored = mgr.restore(3, tree)
        np.testing.assert_allclose(restored["a"], np.asarray(tree["a"]))
        assert restored["b"]["c"] == 3

    def test_async_save(self, tmp_path):
        from repro.checkpoint.checkpointing import CheckpointManager
        mgr = CheckpointManager(str(tmp_path))
        tree = {"w": jnp.ones((128, 128))}
        mgr.save_async(7, tree)
        mgr.wait()
        assert mgr.latest_step() == 7

    def test_torn_write_invisible(self, tmp_path):
        """A crashed writer (tmp dir, no COMMITTED) must be ignored."""
        from repro.checkpoint.checkpointing import CheckpointManager
        mgr = CheckpointManager(str(tmp_path))
        os.makedirs(tmp_path / "step_00000009.tmp")
        os.makedirs(tmp_path / "step_00000005")   # no COMMITTED marker
        assert mgr.latest_step() is None

    def test_restore_casts_dtype(self, tmp_path):
        from repro.checkpoint.checkpointing import CheckpointManager
        mgr = CheckpointManager(str(tmp_path))
        tree = {"w": jnp.ones((4,), jnp.bfloat16)}
        mgr.save(1, tree)
        out = mgr.restore(1, tree)
        assert out["w"].dtype == jnp.bfloat16


class TestFaultTolerance:
    def test_heartbeat_dead_detection(self):
        from repro.runtime.fault_tolerance import HeartbeatMonitor
        clock = [0.0]
        mon = HeartbeatMonitor(timeout_s=10, clock=lambda: clock[0])
        mon.beat(0); mon.beat(1)
        clock[0] = 5.0
        mon.beat(0)
        clock[0] = 12.0
        assert mon.dead_hosts() == [1]
        assert mon.alive_hosts() == [0]

    def test_straggler_detection(self):
        from repro.runtime.fault_tolerance import StragglerDetector
        det = StragglerDetector(min_steps=3)
        for _ in range(5):
            for h in range(4):
                det.record(h, 1.0 if h != 2 else 2.5)
        assert det.stragglers() == [2]

    def test_elastic_mesh_plans(self):
        from repro.runtime.fault_tolerance import plan_elastic_mesh
        shape, axes = plan_elastic_mesh(512)
        assert shape == (2, 16, 16) and axes == ("pod", "data", "model")
        shape, axes = plan_elastic_mesh(256)
        assert shape == (16, 16) and axes == ("data", "model")
        # losing 16 chips: shrink data, keep model
        shape, axes = plan_elastic_mesh(240)
        assert shape == (15, 16)
        assert int(np.prod(shape)) == 240


class TestData:
    def test_deterministic_and_restart_safe(self):
        from repro.data.pipeline import DataConfig, TokenSource
        cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=4, seed=1)
        a, b = TokenSource(cfg), TokenSource(cfg)
        np.testing.assert_array_equal(a.batch(5)["tokens"], b.batch(5)["tokens"])
        assert not np.array_equal(a.batch(5)["tokens"], a.batch(6)["tokens"])

    def test_targets_are_shifted(self):
        from repro.data.pipeline import DataConfig, TokenSource
        src = TokenSource(DataConfig(vocab_size=50, seq_len=8, global_batch=2))
        b = src.batch(0)
        assert b["tokens"].shape == b["targets"].shape == (2, 8)

    def test_markov_learnable(self):
        from repro.data.pipeline import DataConfig, TokenSource
        src = TokenSource(DataConfig(vocab_size=32, seq_len=16,
                                     global_batch=4, kind="markov"))
        b = src.batch(0)
        assert b["tokens"].max() < 32


class TestHloCost:
    def test_matches_xla_on_loopfree(self):
        from repro.runtime.hlo_analysis import analyze
        def f(x, w):
            return jnp.tanh(x @ w) @ w
        c = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32),
                             jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
        mine = analyze(c.as_text())["flops"]
        ca = c.cost_analysis()
        assert mine == pytest.approx(ca["flops"], rel=0.05)

    def test_scan_equals_unroll(self):
        from repro.runtime.hlo_analysis import analyze
        def body(x, w):
            return jnp.tanh(x @ w), None
        def f_scan(x, ws):
            return jax.lax.scan(body, x, ws)[0]
        def f_unroll(x, ws):
            for i in range(6):
                x, _ = body(x, ws[i])
            return x
        xs = jax.ShapeDtypeStruct((32, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((6, 64, 64), jnp.float32)
        fs = analyze(jax.jit(f_scan).lower(xs, ws).compile().as_text())
        fu = analyze(jax.jit(f_unroll).lower(xs, ws).compile().as_text())
        assert fs["flops"] == pytest.approx(fu["flops"], rel=0.02)

    def test_collectives_counted(self):
        from repro.runtime.hlo_analysis import analyze
        mesh = jax.make_mesh((1,), ("data",))
        from jax.sharding import NamedSharding, PartitionSpec as P
        def f(x):
            return jax.lax.with_sharding_constraint(
                x.sum(axis=0, keepdims=True), NamedSharding(mesh, P()))
        # single-device: no collectives expected — just exercise the path
        c = jax.jit(f).lower(jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
        out = analyze(c.as_text())
        assert out["collective_bytes"] >= 0


class TestAnalytic:
    @pytest.mark.parametrize("arch", ["llama3.2-1b", "deepseek-moe-16b",
                                      "rwkv6-3b", "whisper-large-v3",
                                      "jamba-v0.1-52b"])
    def test_param_count_matches_real_tree(self, arch):
        from repro.configs import get_smoke_config
        from repro.models.api import model_fns
        from repro.runtime.analytic import param_count
        cfg = get_smoke_config(arch)
        params = model_fns(cfg).init_params(jax.random.PRNGKey(0))
        real = sum(l.size for l in jax.tree_util.tree_leaves(params))
        pred = param_count(cfg)
        # analytic model ignores norms/biases/mu vectors → small undercount
        assert pred == pytest.approx(real, rel=0.12)

    def test_known_scale_llama405b(self):
        from repro.configs import get_config
        from repro.runtime.analytic import param_count
        n = param_count(get_config("llama3-405b"))
        assert 3.8e11 < n < 4.3e11  # ≈405B

    def test_moe_active_vs_total(self):
        from repro.configs import get_config
        from repro.runtime.analytic import param_count
        cfg = get_config("llama4-maverick-400b-a17b")
        total = param_count(cfg)
        active = param_count(cfg, active=True)
        assert 3.2e11 < total < 4.8e11       # ≈400B
        assert 1.2e10 < active < 2.4e10      # ≈17B
