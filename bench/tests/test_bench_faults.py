"""A whole run at a small size on the CPU (the chip check skipped), with
the timed path broken underneath: ``correct`` has to come out false.

The served cells run on one chip and have no batch to halve and no
exchange between chips, so the faults they can have are a token altered
where it is produced (every third decode step's tokens shifted by one)
and a step that returns its state unchanged (each decode step hands back
the KV cache it was given, so the tokens it decoded are never cached).
The sound run beside them comes out true, and the precision control (the
program's int8 weights and KV) comes out false.

Limits of this small size, from its readings on the CPU over seeds 11-16
(6-second window, a sample of 32 finished requests, 400-850 served
tokens): the sound program's ``max_logit_gap`` read 0.031-0.060 and
``mean_logit_gap`` 0.00016-0.00037; the control's 0.12-0.29 and
0.0017-0.0035. With the cells' 8-request sample the small size reads too
few tokens for the control's mean to stay clear of the sound one's."""

import jax
import jax.numpy as jnp
import pytest

import run
from small import SMALL_ANSWERS, SMALL_MODEL

LIMITS = {"max_logit_gap": 0.1, "mean_logit_gap": 0.0008}
CELL = "pixtral-12b-l20.batch_gen"


@pytest.fixture(autouse=True)
def wide_sample(monkeypatch):
    monkeypatch.setattr(run, "SAMPLE_MAX_REQS", 32)
    monkeypatch.setattr(run, "SAMPLE_TOKENS", 2000)


def go(seed, **kw):
    return run.run_cell(CELL, seed, 6.0, False, m=SMALL_MODEL,
                        mix=SMALL_ANSWERS, require_tpu=False, limits=LIMITS,
                        **kw)


def alter_tokens(engine):
    decode = engine._decode
    calls = [0]

    def altered(*a, **kw):
        tok, ok, cache = decode(*a, **kw)
        calls[0] += 1
        if calls[0] % 3 == 0:
            tok = (tok + 1) % engine.cfg.vocab_size
        return tok, ok, cache
    engine._decode = altered


def keep_cache(engine):
    decode = engine._decode

    def stale(*a, **kw):
        before = jax.tree_util.tree_map(jnp.copy, a[3])  # a[3] is donated
        tok, ok, _ = decode(*a, **kw)
        return tok, ok, before
    engine._decode = stale


def test_sound_run_is_correct():
    res = go(11)
    assert res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] <= LIMITS["max_logit_gap"]
    assert set(res["metrics"]) == {"setup_s", "itl_p95_ms", "output_tok_s"}


def test_altered_tokens_are_not_correct():
    res = go(11, mutate_engine=alter_tokens)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > LIMITS["max_logit_gap"]


def test_unchanged_cache_is_not_correct():
    res = go(11, mutate_engine=keep_cache)
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > \
        LIMITS["mean_logit_gap"]


def test_precision_control_is_not_correct():
    res = go(12, engine_overrides={"weight_dtype": "int8",
                                   "kv_dtype": "int8"})
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > \
        LIMITS["mean_logit_gap"]
