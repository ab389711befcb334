import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell on
512 placeholder CPU devices, proving the distribution config is coherent,
and extract the roofline terms from the compiled artifact.

Per cell this writes experiments/dryrun/<arch>__<shape>__<mesh>.json with:
  * memory_analysis  (bytes per device: args / outputs / temps / peak)
  * xla cost_analysis (raw — undercounts loop bodies, kept for reference)
  * loop-corrected HLO accounting (flops / bytes / collective bytes by op)
    via runtime/hlo_analysis (while bodies × known_trip_count)
  * analytic MODEL_FLOPS (6·N_active·D convention) + params
  * compile wall time

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch.mesh import make_production_mesh
from repro.launch.train import TrainState, make_train_step
from repro.models.api import input_specs, model_fns
from repro.optim import adamw
from repro.runtime import partitioning as part
from repro.runtime import sharding as shard
from repro.runtime.analytic import ideal_bytes_per_chip, model_flops
from repro.runtime.hlo_analysis import analyze

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# v5e hardware constants (roofline denominators; see EXPERIMENTS.md)
PEAK_FLOPS = 197e12     # bf16 / chip
HBM_BW = 819e9          # bytes/s / chip
LINK_BW = 50e9          # bytes/s / ICI link


def _json_default(o):
    if isinstance(o, (jnp.dtype,)):
        return str(o)
    return str(o)


def build_lowering(cfg: ModelConfig, shape: ShapeSpec, mesh):
    """Construct (fn, args, in_shardings, donate) for one cell."""
    fns = model_fns(cfg)
    specs = input_specs(cfg, shape)
    key = jax.random.PRNGKey(0)
    abstract_params = jax.eval_shape(fns.init_params, key)
    fsdp = cfg.num_layers * cfg.d_model >= 126 * 16384  # 405B-class
    pshard = shard.param_shardings(abstract_params, mesh, fsdp=fsdp)
    bshard = shard.batch_shardings(specs["batch"], mesh)
    rep = shard.replicated(mesh)

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig(total_steps=1000)
        abstract_opt = jax.eval_shape(adamw.init, abstract_params)
        admm_state, admm_shard, admm_specs, admm_cfg = None, None, None, None
        if cfg.bcr_keep_frac > 0:
            # the paper's ADMM pruning phase at pod scale: per-leaf Z/U
            # duals (sharded like params) + the penalty term in the loss
            from repro.core import admm as admm_mod
            from repro.launch.train import default_prune_filter
            admm_cfg = admm_mod.ADMMConfig()
            admm_specs = admm_mod.specs_for(abstract_params,
                                            default_prune_filter(cfg))
            admm_state = jax.eval_shape(
                lambda p: admm_mod.admm_init(p, admm_specs), abstract_params)
            zu = jax.tree_util.tree_map_with_path(
                lambda p, s: s if p in admm_specs else None, pshard)
            admm_shard = admm_mod.ADMMState(zu, zu, rep)
        state = TrainState(abstract_params, abstract_opt, admm_state, None)
        state_shard = TrainState(
            pshard, adamw.AdamWState(pshard, pshard, rep), admm_shard, None)
        step = make_train_step(cfg, opt_cfg, admm_cfg, admm_specs)
        metrics_shard = {k: rep for k in ("lr", "grad_norm", "step", "loss")}
        return (step, (state, specs["batch"]), (state_shard, bshard),
                (state_shard, metrics_shard), (0,))

    if shape.kind == "prefill":
        fn = lambda p, b: fns.prefill(p, b)
        return (fn, (abstract_params, specs["batch"]), (pshard, bshard),
                None, ())

    # decode: donate the cache; outputs keep the input cache sharding so the
    # donation aliases (no phantom all-gather of the new cache).
    cshard = shard.cache_shardings(specs["cache"], mesh,
                                   batch=shape.global_batch,
                                   capacity=shape.seq_len)
    fn = lambda p, b, c: fns.decode_step(p, b, c)
    b = shape.global_batch
    logits_shard = jax.NamedSharding(
        mesh, shard.batch_pspec((b, 1, cfg.vocab_size), mesh))
    return (fn, (abstract_params, specs["batch"], specs["cache"]),
            (pshard, bshard, cshard), (logits_shard, cshard), (2,))


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str = OUT_DIR, force: bool = False,
             cfg_override: Optional[ModelConfig] = None,
             tag: str = "") -> Dict[str, Any]:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = f"{arch}__{shape_name}__{mesh_name}{tag}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind in ("prefill", "decode") and cfg_override is None:
        # serving runs in bf16 weights (deploy dtype); training keeps fp32
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind,
    }
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        record.update(status="skipped", reason=why)
        with open(path, "w") as f:
            json.dump(record, f, indent=2, default=_json_default)
        return record

    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        rules = (part.DECODE_RULES if shape.kind == "decode"
                 else part.TRAIN_RULES)
        t0 = time.time()
        with part.use_rules(rules, mesh):
            fn, args, in_shardings, out_shardings, donate = build_lowering(
                cfg, shape, mesh)
            jitted = jax.jit(fn, in_shardings=in_shardings,
                             out_shardings=out_shardings,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        mem_rec = {}
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "peak_memory_in_bytes", "alias_size_in_bytes"):
            val = getattr(mem, field, None)
            if val is not None:
                mem_rec[field] = int(val)
        record["memory_analysis"] = mem_rec or str(mem)

        ca = compiled.cost_analysis() or {}
        record["xla_cost_analysis"] = {
            k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "transcendentals",
             "optimal_seconds", "utilization operand 0 {}")
        }

        hlo = compiled.as_text()
        record["hlo_chars"] = len(hlo)
        corrected = analyze(hlo)
        record["hlo_corrected"] = corrected
        record["analytic"] = model_flops(cfg, shape)
        record["analytic"]["ideal_bytes_per_chip"] = ideal_bytes_per_chip(
            cfg, shape, mesh.devices.size)
        record["timings"] = {"lower_s": t_lower, "compile_s": t_compile}

        n_chips = mesh.devices.size
        # per-device program: corrected numbers are per chip
        compute_s = corrected["flops"] / PEAK_FLOPS
        memory_s = corrected["bytes_accessed"] / HBM_BW
        collective_s = corrected["collective_bytes"] / LINK_BW
        dominant = max(
            (("compute", compute_s), ("memory", memory_s),
             ("collective", collective_s)), key=lambda kv: kv[1])[0]
        record["roofline"] = {
            "n_chips": n_chips,
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": dominant,
            "model_flops_ratio": (
                record["analytic"]["model_flops"]
                / max(corrected["flops"] * n_chips, 1.0)),
        }
        record["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]

    with open(path, "w") as f:
        json.dump(record, f, indent=2, default=_json_default)
    return record


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--all", action="store_true")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--force", action="store_true")
    p.add_argument("--bcr", type=float, default=0.0,
                   help="BCR keep_frac: lowers the ADMM pruning train phase")
    p.add_argument("--out-dir", default=OUT_DIR)
    args = p.parse_args()

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((arch, s, mp))

    for arch, s, mp in cells:
        t0 = time.time()
        override, tag = None, ""
        if args.bcr > 0:
            override = dataclasses.replace(
                get_config(arch), bcr_keep_frac=args.bcr)
            tag = f"__bcr{args.bcr}"
        rec = run_cell(arch, s, multi_pod=mp, out_dir=args.out_dir,
                       force=args.force, cfg_override=override, tag=tag)
        status = rec.get("status")
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f"dom={r['dominant']} comp={r['compute_s']:.3e}s "
                     f"mem={r['memory_s']:.3e}s coll={r['collective_s']:.3e}s")
        elif status == "error":
            extra = rec.get("error", "")[:160]
        elif status == "skipped":
            extra = rec.get("reason", "")[:80]
        print(f"[{time.time()-t0:7.1f}s] {arch:28s} {s:12s} "
              f"{'2x16x16' if mp else '16x16':8s} {status:8s} {extra}",
              flush=True)


if __name__ == "__main__":
    main()
