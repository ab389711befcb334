"""AOT compiles of the main-path Pallas kernels for a described TPU v5e.

Interpret mode accepts block shapes the TPU compiler refuses (minor block
dims that are neither (8, 128)-aligned nor full), so these tests compile
each kernel variant the served path can pick for a v5e chip that is
described, not attached, at llama3.2-1b widths (d_model 2048, d_ff 8192,
32 heads over 8 kv heads of 64, BCR block 128 at keep 0.25, page 16).
Nothing runs; each test asserts the kernel is in the compiled program as a
``tpu_custom_call``.

The topology is described inside a module fixture (only the worker that
runs this file loads the TPU compiler), and the persistent compilation
cache is off around these compiles: their entries cannot be read back
without a chip.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core import BCRSpec, tbcrc_pack
from repro.kernels.bcr_spmm import bcr_spmm, bcr_spmm_grouped
from repro.kernels.paged_decode_attention import (
    paged_decode_attention, paged_prefill_append_attention)
from repro.kernels.plan import (attach_plan, pack_group, quantize_grouped,
                                quantize_packed)

D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 2048, 8192, 32, 8, 64
BLOCK, KEEP, PAGE, BATCH = 128, 0.25, 16, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _packed(n, k, genome, int8):
    """Abstract TBCRC (shapes only) for an (n, k) weight with ``genome``."""
    spec = BCRSpec(block_shape=(BLOCK, BLOCK), keep_frac=KEEP)

    def build(w):
        p = attach_plan(tbcrc_pack(w, spec), genome)
        return quantize_packed(p) if int8 else p
    return jax.eval_shape(build, jax.ShapeDtypeStruct((n, k), jnp.float32))


def _grouped(n, k, members, genome, int8):
    spec = BCRSpec(block_shape=(BLOCK, BLOCK), keep_frac=KEEP)

    def build(w):
        g = pack_group([tbcrc_pack(w[i], spec) for i in range(members)],
                       genome)
        return quantize_grouped(g) if int8 else g
    return jax.eval_shape(
        build, jax.ShapeDtypeStruct((members, n, k), jnp.float32))


def _compiled_kernels(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


@pytest.mark.parametrize("order", ["mij", "imj"])
@pytest.mark.parametrize("int8", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("use_planes", [False, True],
                         ids=["index", "planes"])
def test_bcr_spmm_compiles(one_chip, use_planes, int8, order):
    packed = _packed(D_MODEL, D_MODEL, {"use_planes": use_planes,
                                        "m_tile": 8, "grid_order": order},
                     int8)
    x = jax.ShapeDtypeStruct((BATCH, D_MODEL), jnp.bfloat16,
                             sharding=one_chip)
    hlo = _compiled_kernels(lambda x, p: bcr_spmm(x, p), x,
                            _on(one_chip, packed))
    assert "bcr_spmm" in hlo


@pytest.mark.parametrize("int8", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("use_planes", [False, True],
                         ids=["index", "planes"])
@pytest.mark.parametrize("epilogue", [None, "swiglu"],
                         ids=["plain", "swiglu"])
def test_bcr_spmm_grouped_compiles(one_chip, epilogue, use_planes, int8):
    # swiglu: the gate/up pair (d_ff x d_model); plain: the k/v pair
    n = D_FF if epilogue else KV_HEADS * HEAD_DIM
    grouped = _grouped(n, D_MODEL, 2, {"use_planes": use_planes,
                                       "m_tile": 8}, int8)
    x = jax.ShapeDtypeStruct((BATCH, D_MODEL), jnp.bfloat16,
                             sharding=one_chip)
    hlo = _compiled_kernels(
        lambda x, g: bcr_spmm_grouped(x, g, epilogue=epilogue), x,
        _on(one_chip, grouped))
    assert "bcr_spmm_grouped" in hlo


#: (batch, heads, kv heads, head dim, table width): llama3.2-1b at batch 8;
#: pixtral-12b-l20.batch_gen's decode (24 slots, 8 kv heads of 32, 128
#: columns), its kv heads' share under a mesh of 4, and qwen1.5-4b's MHA
SHAPES = {"llama": (BATCH, HEADS, KV_HEADS, HEAD_DIM, 32),
          "cell": (24, 32, 8, 128, 128), "tp4": (24, 8, 2, 128, 128),
          "mha": (8, 20, 20, 128, 128)}


@pytest.mark.parametrize("suffix,int8,shape", [
    pytest.param(suffix, int8, "llama", id=f"{sid}-{iid}")
    for int8, iid in ((False, "bf16"), (True, "int8"))
    for suffix, sid in ((1, "decode"), (32, "append"))] + [
    pytest.param(1, False, shape, id=f"decode-bf16-{shape}")
    for shape in ("cell", "tp4", "mha")])
def test_paged_attention_compiles(one_chip, suffix, int8, shape):
    batch, heads, kv_heads, head_dim, n_cols = SHAPES[shape]
    n_pages = 1 + batch * n_cols
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = (n_pages, kv_heads, PAGE, head_dim)
    kv = S(pool, jnp.int8 if int8 else jnp.bfloat16)
    scales = (S(pool[:-1], jnp.float32),) * 2 if int8 else ()
    q = S((batch, suffix, heads, head_dim), jnp.bfloat16)
    tables = S((batch, n_cols), jnp.int32)
    lens = S((batch,), jnp.int32)

    def scale_kw(s):
        return dict(k_scale=s[0], v_scale=s[1]) if s else {}
    if suffix == 1:
        fn = lambda q, k, v, bt, ln, *s: paged_decode_attention(
            q, k, v, bt, ln, **scale_kw(s))
        args = (q, kv, kv, tables, lens) + scales
    else:
        fn = lambda q, k, v, bt, pl_, ln, *s: paged_prefill_append_attention(
            q, k, v, bt, pl_, ln, **scale_kw(s))
        args = (q, kv, kv, tables, lens, lens) + scales
    hlo = _compiled_kernels(fn, *args)
    assert "paged_attention" in hlo
