"""Chip smoke test: the BCR-packed, block-paged serving path end to end on a TPU.

    python chip_smoke.py              # one chip: the served main path
    python chip_smoke.py --chips 4    # four chips: mesh-4 engine vs mesh-1

One chip: llama3.2-1b at its published width (16 layers, d_model 2048, 32
heads, 8 kv heads, d_ff 8192, vocab 128256; random weights from seed 0, as
the serving CLIs make them) is built through ``launch/api.py``'s own
builder with ``--bcr-keep 0.25 --bcr-block 128 --page-size 16``, so the
platform picks the compiled Pallas kernels. The script then

* checks that the compiled decode step holds the BCR and paged-attention
  kernels (``tpu_custom_call``),
* runs one prefill and one decode step through the kernels and through the
  pure-JAX references on the same params, and holds the logits to a bf16
  tolerance,
* serves the engine over the real HTTP front end in a thread, sends every
  prompt once non-streaming and once as SSE, all at once, and checks that
  every request FINISHED, that both replies carry the same greedy tokens,
  that the supervisor never restarted the step loop, and that the drained
  engine conserves its slots and pages.

``--chips 4`` runs only the tensor-parallel phase: the same prompts through
the mesh-4 sharded engine and the mesh-1 engine, greedy tokens compared.

Any failed check exits non-zero. With no TPU it exits non-zero at once and
prints no result. The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH_FLAGS = ["--arch", "llama3.2-1b", "--bcr-keep", "0.25",
              "--bcr-block", "128", "--page-size", "16"]
SLOTS, CAPACITY = 4, 512
PROMPT_LENS = (200, 231, 256)       # one prefill bucket (256)
GEN = 16
SEED = 0
# kernels vs references, both in bf16 activations: the kernels round the
# fp32 packed weights to bf16 on the MXU while the references multiply
# in fp32, so logits may differ by a few bf16 ulps of their scale
LOGIT_RTOL = 2.0 ** -5
KERNELS = ("bcr_spmm", "bcr_spmm_grouped", "paged_attention")
MESH = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def prompts(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, size=n).astype(np.int32).tolist()
            for n in PROMPT_LENS]


def engine_args():
    """The serving CLI's own flags, parsed by ``launch/api.py``'s parser."""
    from repro.launch import api
    return api.build_parser().parse_args(
        ARCH_FLAGS + ["--slots", str(SLOTS), "--capacity", str(CAPACITY)])


def kernel_names(hlo: str):
    return sorted(set(re.findall(
        r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*custom_call_target="
        r"\"tpu_custom_call\"", hlo)))


def decode_kernels(engine):
    """Kernel names in the engine's compiled decode step (widest table)."""
    import jax.numpy as jnp
    n = engine.ec.n_slots
    zeros = jnp.zeros((n,), jnp.float32)
    lowered = engine._decode.lower(
        engine.params, jnp.zeros((n, 1), jnp.int32),
        jnp.zeros((n,), jnp.int32), engine.pool.cache, engine._next_key(),
        zeros, zeros.astype(jnp.int32),
        jnp.zeros((n, engine.pool.max_pages), jnp.int32), use_topk=False)
    return kernel_names(lowered.compile().as_text())


def compare(name, got, want):
    """Max |Δlogit| of the kernel path against the reference, and whether
    its greedy token agrees (or is a tie within the tolerance)."""
    import numpy as np
    got = np.asarray(got, np.float32).reshape(-1)
    want = np.asarray(want, np.float32).reshape(-1)
    if not np.isfinite(got).all():
        fail(f"{name}: non-finite kernel logits")
    diff = float(np.abs(got - want).max())
    tol = LOGIT_RTOL * max(1.0, float(np.abs(want).max()))
    g, w = int(got.argmax()), int(want.argmax())
    log(f"{name}: max |logit kernel - ref| = {diff!r} (tol {tol!r}); "
        f"greedy token kernel {g} ref {w} match={g == w}")
    if diff > tol:
        fail(f"{name}: kernel logits differ from the reference by {diff} "
             f"> {tol}")
    if g != w and want[g] < want[w] - tol:
        fail(f"{name}: greedy token {g} is not within tolerance of the "
             f"reference's {w}")
    return g


def reference_check(engine, prompt):
    """One prefill and one decode step through the kernels and through the
    pure-JAX references (same params, same paged cache)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.models.api import model_fns
    from repro.serving.kv_slots import PagedSlotPool
    cfg = engine.cfg
    ref_cfg = dataclasses.replace(cfg, kernel_impl="ref", attn_impl="flash")
    fk, fr = engine.fns, model_fns(ref_cfg)
    log(f"reference check: kernels kernel_impl={cfg.kernel_impl} "
        f"attn_impl={cfg.attn_impl}; references kernel_impl=ref "
        f"attn_impl=flash")
    toks = jnp.asarray([prompt], jnp.int32)
    lk, pcache = jax.jit(fk.prefill)(engine.params, {"tokens": toks})
    lr, _ = jax.jit(fr.prefill)(engine.params, {"tokens": toks})
    nxt = compare("prefill", lk[0, -1], lr[0, -1])
    pool = PagedSlotPool(fk.init_cache, 1, engine.ec.capacity,
                         page_size=engine.ec.page_size)
    assert pool.reserve(0, len(prompt) + 1)
    pool.insert(pcache, 0, len(prompt))
    pool.ensure(0, len(prompt) + 1)
    batch = {"tokens": jnp.asarray([[nxt]], jnp.int32),
             "cache_len": jnp.asarray(pool.lens),
             "block_tables": pool.device_tables()}
    dk, _ = jax.jit(fk.decode_step)(engine.params, batch, pool.cache)
    dr, _ = jax.jit(fr.decode_step)(engine.params, batch, pool.cache)
    compare("decode", dk[0, -1], dr[0, -1])


def serve_over_http(engine, prompts_):
    """Every prompt non-streaming and as SSE, concurrently, through the
    HTTP front end; returns (results, restarts, conservation_ok)."""
    from repro.serving.server import (ServerConfig, http_request,
                                      start_in_thread, stream_completion)
    handle = start_in_thread(engine, ServerConfig(port=0))
    port = handle.port

    def plain(p):
        status, _, body = http_request(
            "127.0.0.1", port, "POST", "/v1/completions",
            {"prompt": p, "max_tokens": GEN}, timeout=600.0)
        return status, body.get("status"), body.get("tokens", [])

    def sse(p):
        r = stream_completion("127.0.0.1", port,
                              {"prompt": p, "max_tokens": GEN},
                              timeout=600.0)
        return r.status, (r.final or {}).get("status"), r.tokens

    t0 = time.perf_counter()
    with ThreadPoolExecutor(2 * len(prompts_)) as ex:
        futs = [(ex.submit(plain, p), ex.submit(sse, p)) for p in prompts_]
        results = [(a.result(), b.result()) for a, b in futs]
    elapsed = time.perf_counter() - t0
    handle.request_drain()
    handle.wait_closed(timeout=120.0)
    n_tok = sum(len(r[2]) for pair in results for r in pair)
    log(f"http: {2 * len(prompts_)} requests, {n_tok} tokens in "
        f"{elapsed!r} s (host clock, including queueing)")
    return results, handle.server.host.restarts, handle.server.conservation_ok


def run_one_chip() -> None:
    from repro.launch import api
    t0 = time.perf_counter()
    args = engine_args()
    engine = api.build_api_engine(args)
    cfg = engine.cfg
    log(f"model {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads} heads, {cfg.num_kv_heads} kv heads, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; bcr keep {cfg.bcr_keep_frac} "
        f"block {cfg.bcr_block}; kernel_impl={cfg.kernel_impl} "
        f"attn_impl={cfg.attn_impl}; slots {SLOTS}, capacity {CAPACITY}, "
        f"page_size {engine.ec.page_size}")
    log(f"build (init + pack + plan) seconds: {time.perf_counter() - t0!r}")
    ps = prompts(cfg.vocab_size)

    t0 = time.perf_counter()
    engine.warmup([len(p) for p in ps], gen=GEN)
    log(f"warmup (compile) seconds: {time.perf_counter() - t0!r}")

    names = decode_kernels(engine)
    log(f"tpu_custom_call kernels in the compiled decode step: {names}")
    missing = [k for k in KERNELS if k not in names]
    if missing:
        fail(f"decode step lacks kernels {missing}")

    reference_check(engine, ps[0])

    results, restarts, conserved = serve_over_http(engine, ps)
    for i, ((hs, st, toks), (ss, sst, stoks)) in enumerate(results):
        log(f"prompt {i} ({len(ps[i])} tokens): non-stream HTTP {hs} {st} "
            f"{toks}; stream HTTP {ss} {sst} {stoks}")
        if (hs, ss) != (200, 200) or (st, sst) != ("FINISHED", "FINISHED"):
            fail(f"prompt {i}: not FINISHED ({hs} {st} / {ss} {sst})")
        if len(toks) != GEN or toks != stoks:
            fail(f"prompt {i}: stream and non-stream tokens differ")
    log(f"supervisor restarts: {restarts}; conservation: "
        f"{'ok' if conserved else 'FAILED'}; engine failed requests: "
        f"{engine.stats['failed']}")
    if restarts != 0:
        fail(f"engine step loop restarted {restarts} times")
    if not conserved:
        fail("conservation check failed after drain")


def run_four_chips() -> None:
    """Mesh-4 tensor-parallel engine against the mesh-1 engine on the same
    params and prompts: greedy tokens, and the first prompt's prefill
    logits."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.serve import build_config, build_params
    from repro.models.api import model_fns
    from repro.serving import EngineConfig, InferenceEngine
    from repro.serving import tp as tp_lib
    if len(jax.devices()) < MESH:
        fail(f"--chips 4 needs {MESH} devices, found {len(jax.devices())}")
    t0 = time.perf_counter()
    args = engine_args()
    cfg = build_config(args)
    params = build_params(cfg, log, decode_m=SLOTS)
    log(f"build seconds: {time.perf_counter() - t0!r}")
    ps = prompts(cfg.vocab_size)
    outs, engines = {}, {}
    for mesh in (1, MESH):
        t0 = time.perf_counter()
        eng = InferenceEngine(cfg, params, EngineConfig(
            n_slots=SLOTS, capacity=CAPACITY, page_size=16,
            mesh_model=mesh))
        outs[mesh] = eng.generate(ps, max_new_tokens=GEN)
        engines[mesh] = eng
        log(f"mesh {mesh}: {time.perf_counter() - t0!r} s (compile "
            f"included); tokens {outs[mesh]}")
        eng.check_conservation()

    # prefill logits of the first prompt, both layouts
    toks = jnp.asarray([ps[0]], jnp.int32)
    l1, _ = jax.jit(model_fns(cfg).prefill)(params, {"tokens": toks})
    e4 = engines[MESH]
    local = model_fns(tp_lib.localize_cfg(cfg, MESH))
    prefill4 = tp_lib.ShardedProgram(
        lambda p, t: local.prefill(p, {"tokens": t})[0], e4._mesh,
        in_specs=(e4._param_specs, P()), out_specs=P())
    l4 = prefill4(e4.params, toks)
    diff = float(np.abs(np.asarray(l4, np.float32)
                        - np.asarray(l1, np.float32)).max())
    log(f"prefill logits max |mesh{MESH} - mesh1| = {diff!r}")
    same = outs[1] == outs[MESH]
    log(f"greedy tokens mesh-{MESH} == mesh-1: {same}")
    if not same:
        for i, (a, b) in enumerate(zip(outs[1], outs[MESH])):
            if a != b:
                j = next(k for k, (x, y) in enumerate(zip(a, b)) if x != y)
                log(f"first divergence: prompt {i}, token {j}: mesh-1 "
                    f"{a[j]} vs mesh-4 {b[j]}")
                break
        fail("mesh-4 and mesh-1 greedy tokens differ")


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4],
                   help="1: the served main path on one chip; 4: only the "
                        "mesh-4 vs mesh-1 tensor-parallel comparison")
    args = p.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    log(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"device count {len(jax.devices())}")
    if dev.platform != "tpu":
        fail(f"no TPU found (platform {dev.platform!r}); this smoke test "
             f"has no CPU fallback")
    from repro.launch.serve import enable_compile_cache
    log(f"compilation cache: {enable_compile_cache()}")
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
