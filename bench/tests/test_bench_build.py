"""The chunked on-device build against the program's own whole build, at a
size the CPU holds."""

import jax
import jax.numpy as jnp
import numpy as np

from lib import build
from small import SMALL_MODEL


def test_chunked_build_matches_the_programs_tree_and_values():
    m1 = dict(SMALL_MODEL, build_chunk_layers=1)
    m2 = dict(SMALL_MODEL, build_chunk_layers=2)
    cfg = build.program_config(m1)
    a = build.build_params(cfg, m1, seed=7, decode_m=4)
    b = build.build_params(cfg, m2, seed=7, decode_m=4)
    ta, tb = (jax.tree_util.tree_structure(x) for x in (a, b))
    assert ta == tb
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # the program's whole build: dense init of every layer, then its packer
    from repro.launch.serve import pack_params
    from repro.models.api import model_fns
    whole = jax.eval_shape(lambda: pack_params(
        cfg, model_fns(cfg).init_params(jax.random.PRNGKey(0)), decode_m=4))
    assert jax.tree_util.tree_structure(whole) == ta
    for x, y in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(a)):
        assert (x.shape, x.dtype) == (y.shape, y.dtype)


def test_packer_keeps_exactly_the_seeded_support():
    """The seeded weights are balanced BCR already, so packing loses
    nothing: unpacked, the program's weights equal the seeded ones."""
    from reference import seeded
    from repro.core.bcrc import tbcrc_unpack
    m = SMALL_MODEL
    cfg = build.program_config(m)
    p = build.build_params(cfg, m, seed=3, decode_m=4)
    want = seeded.head(seeded.root_key(3), m)["lm_head"]
    got = tbcrc_unpack(p["lm_head"]["w_packed"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_engine_serves_the_chunked_build():
    m = SMALL_MODEL
    cfg = build.program_config(m)
    eng = build.build_engine(cfg, m, build.build_params(cfg, m, 5, 4), 5)
    out = eng.generate([[1, 2, 3, 4, 5], [7] * 20], max_new_tokens=4)
    assert [len(o) for o in out] == [4, 4]
    eng.check_conservation()
