"""Build the program under test for a cell: its config, its BCR-packed
weights (made on the device from the seed, in layer chunks, through the
program's own ``pack_params``), and the engine.

The program's ``launch/serve.py:build_params`` is not used: it runs
``init_params`` for the whole dense fp32 model on the device before
packing, which does not fit one chip for these models. Here each chunk of
layers is generated dense (``reference/seeded.py``), packed, plan-tuned
and fused by ``pack_params`` in one jitted call, and written into a
preallocated packed stack; the whole dense model never exists at once.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from reference import seeded

#: published config keys -> the program's ModelConfig fields
_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
         "num_attention_heads": "num_heads",
         "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
         "intermediate_size": "d_ff", "vocab_size": "vocab_size",
         "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
         "qkv_bias": "qkv_bias"}


def program_config(m: Dict[str, Any], impl: Optional[str] = None):
    """The program's ModelConfig for config file ``m``: the repo preset
    named by ``arch`` with every size from the file, BCR from the file,
    and the kernel backend the platform selects."""
    from repro.configs import get_config
    from repro.launch.serve import select_backend
    cfg = get_config(m["arch"])
    cfg = dataclasses.replace(
        cfg, **{f: m[k] for k, f in _KEYS.items()},
        bcr_keep_frac=float(m["bcr_keep"]),
        bcr_block=(int(m["bcr_block"]),) * 2)
    kernel_impl, attn_impl = select_backend(impl,
                                            int(m["engine"]["page_size"]))
    cfg = dataclasses.replace(cfg, kernel_impl=kernel_impl)
    if attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    return cfg


def _program_layer(w: Dict[str, jax.Array], qkv_bias: bool
                   ) -> Dict[str, Any]:
    """Seeded layer weights in the program's param-tree layout."""
    def lin(name, bias=None):
        out = {"w": w[name]}
        if bias is not None:
            out["b"] = w[bias]
        return out
    b = qkv_bias
    return {
        "norm1": {"scale": w["n1"]},
        "mixer": {"wq": lin("wq", "bq" if b else None),
                  "wk": lin("wk", "bk" if b else None),
                  "wv": lin("wv", "bv" if b else None),
                  "wo": lin("wo")},
        "norm2": {"scale": w["n2"]},
        "ffn": {"wg": lin("wg"), "wi": lin("wi"), "wo": lin("wd")},
    }


def _seed_model(m: Dict[str, Any]) -> Dict[str, Any]:
    return {k: m[k] for k in ("hidden_size", "intermediate_size",
                              "num_attention_heads", "num_key_value_heads",
                              "head_dim", "qkv_bias", "vocab_size",
                              "bcr_block", "bcr_keep")}


def build_params(cfg, m: Dict[str, Any], seed: int, decode_m: int,
                 weight_dtype: str = "") -> Dict[str, Any]:
    """Packed params for the whole model, chunk by chunk on the device.
    ``weight_dtype="int8"`` runs the packer's own int8 quantization on each
    chunk (the precision control), so the fp32 tiles of the whole model and
    their int8 codes never coexist on the device."""
    from repro.launch.serve import pack_params
    sm = _seed_model(m)
    n_layers = int(m["num_hidden_layers"])
    chunk = int(m["build_chunk_layers"])
    if n_layers % chunk:
        raise ValueError(f"build_chunk_layers {chunk} must divide "
                         f"{n_layers} layers")

    @jax.jit
    def head_fn(root):
        h = seeded.head(root, sm)
        packed = pack_params(cfg, {"lm_head": {"w": h["lm_head"]}},
                             decode_m=decode_m, weight_dtype=weight_dtype)
        return {"embed": {"table": h["embed"]},
                "final_norm": {"scale": h["final_norm"]},
                "lm_head": packed["lm_head"]}

    @jax.jit
    def chunk_fn(root, ids):
        dense = jax.vmap(lambda i: _program_layer(
            seeded.layer(root, i, sm), sm["qkv_bias"]))(ids)
        return pack_params(cfg, {"stack": [dense]}, decode_m=decode_m,
                           weight_dtype=weight_dtype)["stack"]

    root = seeded.root_key(seed)
    ids0 = jnp.arange(chunk, dtype=jnp.int32)
    shapes = jax.eval_shape(chunk_fn, root, ids0)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)

    @jax.jit
    def zeros_fn():
        return jax.tree_util.tree_unflatten(treedef, [
            jnp.zeros((n_layers,) + s.shape[1:], s.dtype) for s in leaves])

    @functools.partial(jax.jit, donate_argnums=(0,))
    def put_fn(full, part, start):
        return jax.tree_util.tree_map(
            lambda f, p: jax.lax.dynamic_update_slice_in_dim(f, p, start, 0),
            full, part)

    params = head_fn(root)          # largest transient first, while free
    stack = zeros_fn()
    for start in range(0, n_layers, chunk):
        part = chunk_fn(root, ids0 + start)
        stack = put_fn(stack, part, jnp.int32(start))
        del part
    params["prefix"] = []
    params["stack"] = stack
    jax.block_until_ready(params)
    return params


def build_engine(cfg, m: Dict[str, Any], params, seed: int,
                 **overrides):
    """The engine with the config file's engine settings; ``overrides``
    set further EngineConfig fields (the precision control's int8 path)."""
    from repro.serving import EngineConfig, InferenceEngine
    e = dict(m["engine"])
    ec = EngineConfig(n_slots=e["n_slots"], capacity=e["capacity"],
                      page_size=e["page_size"], kv_pages=e.get("kv_pages"),
                      max_admit_per_step=e.get("max_admit_per_step"),
                      seed=int(seed) & 0x7FFFFFFF, **overrides)
    return InferenceEngine(cfg, params, ec)


def warm_up(engine, prompt_lens, output_lens) -> Dict[str, int]:
    """Compile (and run once) exactly the programs this cell's traffic can
    reach: prefill buckets of its prompt lengths x the admission row
    tiers up to ``max_admit_per_step``, the pool inserts that go with
    them, and the greedy decode program at every block-table width its
    contexts can span. Then wipe the engine's bookkeeping."""
    import numpy as np
    ec = engine.ec
    cap = ec.capacity
    buckets = sorted({engine._bucket(int(p)) for p in prompt_lens})
    most = ec.max_admit_per_step or ec.n_slots
    tiers = [t for t in engine._row_tiers() if t < most]
    tiers.append(next(t for t in engine._row_tiers() if t >= most))
    for b in buckets:
        for t in tiers:
            engine.generate([np.zeros((min(b, cap - 2),), np.int32)] * t,
                            max_new_tokens=2)
    n_prefill = len(buckets) * len(tiers)
    widths = []
    if engine.paged:
        pool = engine.pool
        lo = pool.pages_needed(min(prompt_lens) + 1)
        hi = pool.pages_needed(min(cap, max(prompt_lens) + max(output_lens)))
        pow2 = engine._pow2_widths()
        wmin = next(w for w in pow2 if w >= lo)
        wmax = next(w for w in pow2 if w >= hi)
        widths = [w for w in pow2 if wmin <= w <= wmax]
        n = ec.n_slots
        zeros = jnp.zeros((n,), jnp.float32)
        for w in widths:
            _, _, pool.cache = engine._decode(
                engine.params, jnp.zeros((n, 1), jnp.int32),
                jnp.zeros((n,), jnp.int32), pool.cache, engine._next_key(),
                zeros, zeros.astype(jnp.int32),
                jnp.zeros((n, w), jnp.int32), use_topk=False)
    jax.block_until_ready(engine.pool.cache)
    engine.sched.finished.clear()
    engine._step_time = 0.0
    engine.reset_stats()
    return {"prefill_programs": n_prefill, "decode_widths": len(widths),
            "buckets": buckets, "tiers": tiers, "widths": widths}
