"""A second architecture family, added by this file alone: no harness file
names it or changes for it.

The toy family is a dense decoder whose first layer the program keeps
unstacked, in ``params["prefix"]``, as it keeps the leading dense layers
of a model with experts (``moe_first_dense``); the other layers are
stacked. The program makes a prefix only for a model with experts, so the
toy states one expert and places the first expert layer past its last
layer (``moe_every``): every FFN stays dense, and the plain dense
reference applies. The family is registered under ``families.`` and
``reference.`` for the test's length, then built, served and compared
with its reference through ``run.run_cell`` on the CPU.
"""

import sys
import types

import jax
import numpy as np
import pytest

import run
from families import dense_decoder
from lib import build
from reference import dense_decoder as dense_reference
from reference import seeded
from small import SMALL_ANSWERS, SMALL_MODEL

NAME = "toy_prefix"
TOY = dict(SMALL_MODEL, name="toy", reference=NAME, num_hidden_layers=3,
           build_chunk_layers=1, unstacked_layers=1, num_experts=1,
           expert_every=4)
#: the small size's limits (``test_bench_faults.py``)
LIMITS = {"max_logit_gap": 0.1, "mean_logit_gap": 0.0008}
CELL = "pixtral-12b-l20.batch_gen"


def toy_family(first_layer=0):
    """The family module; ``first_layer`` is the seeded layer that the
    prefix holds (0 is the sound family)."""
    fam = types.ModuleType(f"families.{NAME}")
    fam.KEYS = dict(dense_decoder.KEYS, unstacked_layers="moe_first_dense",
                    num_experts="num_experts", expert_every="moe_every")
    fam.head = dense_decoder.head
    fam.stack = dense_decoder.stack
    fam.WORK = dense_decoder.WORK

    def prefix(root, m):
        return [dense_decoder.layer(seeded.layer(root, first_layer, m),
                                    m["qkv_bias"])]
    fam.prefix = prefix
    return fam


@pytest.fixture
def register(monkeypatch):
    def put(fam):
        monkeypatch.setitem(sys.modules, f"families.{NAME}", fam)
        monkeypatch.setitem(sys.modules, f"reference.{NAME}",
                            dense_reference)
    put(toy_family())
    monkeypatch.setattr(run, "SAMPLE_MAX_REQS", 32)
    monkeypatch.setattr(run, "SAMPLE_TOKENS", 2000)
    return put


def test_toy_builds_a_prefix_and_a_stack_of_the_programs_tree(register):
    cfg = build.program_config(TOY)
    p = build.build_params(cfg, TOY, seed=7, decode_m=4)
    assert len(p["prefix"]) == 1 and len(p["stack"]) == 1
    assert {x.shape[0] for x in jax.tree_util.tree_leaves(p["stack"])} \
        == {2}
    from repro.launch.serve import pack_params
    from repro.models.api import model_fns
    whole = jax.eval_shape(lambda: pack_params(
        cfg, model_fns(cfg).init_params(jax.random.PRNGKey(0)), decode_m=4))
    assert jax.tree_util.tree_structure(whole) == \
        jax.tree_util.tree_structure(p)
    for x, y in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(p)):
        assert (x.shape, x.dtype) == (y.shape, y.dtype)
    # the prefix holds layer 0, the stack's rows layers 1 and 2 (made
    # outside a jitted call here, so equal to rounding)
    from repro.core.bcrc import tbcrc_unpack
    root = seeded.root_key(7)
    for i, lp in [(0, p["prefix"][0])] + [
            (1 + r, jax.tree_util.tree_map(lambda x, r=r: x[r],
                                           p["stack"][0])) for r in range(2)]:
        got = tbcrc_unpack(lp["ffn"]["wo"]["w_packed"])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(seeded.layer(root, i, TOY)["wd"]),
            rtol=1e-6, atol=0)


@pytest.mark.parametrize("first_layer, correct", [(0, True), (2, False)])
def test_toy_is_served_and_compared_with_its_reference(register,
                                                       first_layer, correct):
    """Sound, the toy is correct; with its prefix holding another layer's
    weights than the reference's layer 0, it is not."""
    register(toy_family(first_layer))
    res = run.run_cell(CELL, 21, 6.0, False, m=TOY, mix=SMALL_ANSWERS,
                       require_tpu=False, limits=LIMITS)
    assert res["correct"] is correct, res["checks"]
    assert set(res["metrics"]) == {"setup_s", "itl_p95_ms", "output_tok_s"}
