#!/usr/bin/env python3
"""Compile a configuration's decode step and its largest prefill for a
described v5e chip, without one, and print their memory analyses and the
Pallas kernels (``tpu_custom_call``) in the decode step.

    JAX_PLATFORMS=cpu python3 bench/aot_rehearse.py bench/configs/<config>.json [--rows R]

Shapes are abstract at full width (``jax.eval_shape`` over the harness's
chunked build), so nothing of the model is allocated here. The programs
are the model's ``decode_step`` over the block-paged pool at the engine's
slots and widest table, and ``prefill`` of ``R`` rows (default: the
config's ``max_admit_per_step``) at bucket ``B`` (default: the capacity's), with the
engine's fused greedy sampling left out. Nothing runs: this says what
the chip's compiler accepts and how much memory each program needs, not
how fast it is.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("config")
    p.add_argument("--rows", type=int, default=0)
    p.add_argument("--bucket", type=int, default=0,
                   help="prefill bucket (default: the capacity's)")
    a = p.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    from lib import build
    from repro.models.api import model_fns
    with open(a.config) as f:
        m = json.load(f)
    cfg = build.program_config(m, "ref")
    cfg = dataclasses.replace(cfg, kernel_impl="pallas", attn_impl="paged")
    e = m["engine"]
    n, cap, ps = e["n_slots"], e["capacity"], e["page_size"]
    max_pages = -(-cap // ps)
    kv_pages = e.get("kv_pages") or n * max_pages + 1
    rows = a.rows or e.get("max_admit_per_step") or n

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def put(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    # abstract packed params through the harness's own build functions
    from reference import seeded
    head, prefix, stack = build.packers(cfg, m, decode_m=n)
    root = jax.eval_shape(lambda: seeded.root_key(0))
    params = jax.eval_shape(head, root)
    params["prefix"] = jax.eval_shape(prefix, root)
    layers = jnp.arange(len(params["prefix"]), m["num_hidden_layers"],
                        dtype=jnp.int32)
    params["stack"] = jax.eval_shape(stack, root, layers)
    leaves = jax.tree_util.tree_leaves(params)
    pbytes = sum(x.size * x.dtype.itemsize for x in leaves)
    print(f"{m['name']}: packed params {pbytes} B "
          f"({len(leaves)} leaves)", flush=True)
    fns = model_fns(cfg)
    cache = jax.eval_shape(lambda: fns.init_cache(
        n, cap, kv_pages=kv_pages, page_size=ps))
    cbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree_util.tree_leaves(cache))
    print(f"KV pool: {kv_pages} pages x {ps} = {kv_pages * ps} tokens, "
          f"{cbytes} B", flush=True)
    i32 = jnp.int32

    def decode(p, toks, lens, c, bt):
        logits, c = fns.decode_step(p, {"tokens": toks, "cache_len": lens,
                                        "block_tables": bt,
                                        "token_mask": (lens > 0)[:, None]},
                                    c)
        return jnp.argmax(logits[:, -1], -1), c

    S = jax.ShapeDtypeStruct
    args = (put(params), S((n, 1), i32, sharding=one),
            S((n,), i32, sharding=one), put(cache),
            S((n, max_pages), i32, sharding=one))
    dec = jax.jit(decode, donate_argnums=(3,)).lower(*args).compile()
    kern = sorted(set(re.findall(
        r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*custom_call_target="
        r"\"tpu_custom_call\"", dec.as_text())))
    print(f"decode step ({n} slots, table width {max_pages}): "
          f"{dec.memory_analysis()}", flush=True)
    print(f"decode step tpu_custom_call kernels: {kern}", flush=True)

    bucket = 8
    while bucket < cap:
        bucket *= 2
    bucket = a.bucket or min(bucket, cap)

    def prefill(p, toks, length):
        logits, pc = fns.prefill(p, {"tokens": toks, "length": length})
        return jnp.argmax(logits[:, -1], -1), pc

    pre = jax.jit(prefill).lower(
        put(params), S((rows, bucket), i32, sharding=one),
        S((rows,), i32, sharding=one)).compile()
    print(f"prefill ({rows} rows x {bucket}): {pre.memory_analysis()}",
          flush=True)


if __name__ == "__main__":
    main()
