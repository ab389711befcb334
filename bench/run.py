#!/usr/bin/env python3
"""Chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration (``bench/configs/<config>.json``) under a traffic
mix (``bench/traffic/<mix>.json``). One run:

1. finds the chips the cell asks for, or exits non-zero with no result;
2. makes the configuration's BCR-pruned weights on the device from the
   seed, in the layer tree of the family that its ``reference`` key names
   (``families/``), and packs them with the program's own packer, in
   layer chunks;
3. builds the program's ``InferenceEngine`` with the config's engine
   settings, warms up exactly the programs the mix can reach, and serves
   it over the program's HTTP front end (``serving/server.py``);
4. starts the stdlib-only load client (``lib/client.py``) as a child
   process, which sends the mix for its warm-in plus ``--seconds``;
5. with ``--trace 1``, profiles a few seconds in the middle of the window
   and reduces the trace (``lib/trace.py``), pairing each decode with the
   program's own ``engine.decode.dispatch`` span;
6. drains the server, frees the program's state, and compares a sample of
   the served answers with the plain reference (``reference/``);
7. prints the metrics that ``BENCHMARK.json`` lists for the cell (read by
   ``metrics/<name>.py``) as the last stdout line, and each number
   compared beside its limit as the last stderr lines.

Progress goes to stderr. The persistent compile cache is ``.jax_cache/``
at the root of the checkout.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

T_PROCESS = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from lib import stats, traffic  # noqa: E402
from lib import trace as trace_lib  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(BENCH, ".traces")
#: longest stretch of the window that a traced run profiles
TRACE_SECONDS = 15.0
#: the correctness sample: the longest finished request, then others
#: drawn from the seed until it holds SAMPLE_TOKENS served tokens and
#: SAMPLE_MIN_REQS requests, or SAMPLE_MAX_REQS requests
SAMPLE_TOKENS = 1536
SAMPLE_MIN_REQS = 4
SAMPLE_MAX_REQS = 8


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> Any:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> Dict[str, Any]:
    """The cell's entry, configuration, mix and metric lists, by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def mine(metrics):
        return [x for x in metrics
                if name in x.get("workloads", [name])]
    return {"cell": cell, "config_file": os.path.join(ROOT, conf["file"]),
            "mix_file": os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"),
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def read_metric(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx)


class CompileCounter:
    """Counts backend compiles (cache hits included) and retraces."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.compiles = self.traces = 0
        self._lock = threading.Lock()
        self._names = {dispatch.BACKEND_COMPILE_EVENT: "compiles",
                       dispatch.JAXPR_TRACE_EVENT: "traces"}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, _dur: float, **_kw) -> None:
        key = self._names.get(event)
        if key:
            with self._lock:
                setattr(self, key, getattr(self, key) + 1)

    def snapshot(self):
        with self._lock:
            return self.compiles, self.traces


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and (d.platform != "tpu" or len(devs) < chips):
        log(f"FAIL: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {d.platform} device(s). No CPU fallback.")
        sys.exit(2)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def peak_bytes() -> Optional[int]:
    import jax
    try:
        return max(int(d.memory_stats()["peak_bytes_in_use"])
                   for d in jax.local_devices())
    except (TypeError, KeyError, AttributeError):
        return None


def sample_requests(reqs: List[Dict[str, Any]], seed: int
                    ) -> List[Dict[str, Any]]:
    """Finished requests to compare: the longest, then others drawn from
    the seed (see SAMPLE_TOKENS)."""
    fin = [r for r in reqs if stats.ok(r)]
    if not fin:
        return []
    fin.sort(key=lambda r: (-len(r["tokens"]), r["i"]))
    out, rest = [fin[0]], fin[1:]
    random.Random(seed ^ 0x5EED).shuffle(rest)
    for r in rest:
        if len(out) >= SAMPLE_MAX_REQS or (
                len(out) >= SAMPLE_MIN_REQS
                and sum(len(x["tokens"]) for x in out) >= SAMPLE_TOKENS):
            break
        out.append(r)
    return out


def compare(m: Dict[str, Any], seed: int, sample: List[Dict[str, Any]],
            plan: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The reference's reading of every served token in the sample: how
    far its logit lies below the reference's best at that position."""
    ref = importlib.import_module("reference." + m["reference"])
    vocab = m["vocab_size"]
    prompts = [traffic.prompt_tokens(plan[r["i"]], vocab) for r in sample]
    served = [r["tokens"] for r in sample]
    longest = max(len(p) + len(s) - 1 for p, s in zip(prompts, served))
    pad = 256
    while pad < longest:
        pad *= 2
    gaps = ref.served_gaps(m, seed, prompts, served, pad)
    flat = [float(g) for gs in gaps for g in gs]
    return {"numbers": {
                "max_logit_gap": max(flat),
                "mean_logit_gap": sum(flat) / len(flat),
                "mismatch_share": sum(g > 0 for g in flat) / len(flat)},
            "n_tokens": len(flat), "n_requests": len(sample), "pad": pad,
            "per_request": [float(gs.max()) for gs in gaps]}


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             m: Optional[Dict[str, Any]] = None,
             require_tpu: bool = True,
             mix: Optional[Dict[str, Any]] = None,
             limits: Optional[Dict[str, float]] = None,
             engine_overrides: Optional[Dict[str, Any]] = None,
             mutate_engine=None) -> Dict[str, Any]:
    """One run of a cell; returns the result object (see module doc).
    ``m``, ``mix`` and ``limits`` replace the cell's configuration, traffic
    and limit (tests at a small size),
    ``engine_overrides`` set further EngineConfig fields (the precision
    control), and ``mutate_engine(engine)`` plants a fault (tests)."""
    c = load_cell(cell_name)
    cell = c["cell"]
    dev = device_info(int(cell["chips"]), require_tpu)
    import jax
    # a fresh checkout has no cache directory, and JAX does not make one
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter()
    if m is None:
        with open(c["config_file"]) as f:
            m = json.load(f)
    mix_file = c["mix_file"]
    if mix is None:
        mix = traffic.load_mix(mix_file)
    else:
        mix_file = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                                f"bench_mix_{os.getpid()}.json")
        with open(mix_file, "w") as f:
            json.dump(mix, f)
    peaks = load_json("peaks.json")["devices"].get(dev["kind"])
    if peaks is None and require_tpu:
        raise SystemExit(f"no peaks for device kind {dev['kind']!r} in "
                         f"bench/peaks.json")
    from lib import build
    from repro.serving.server import ServerConfig, start_in_thread

    t = time.perf_counter()
    cfg = build.program_config(m)
    params = build.build_params(
        cfg, m, seed, m["engine"]["n_slots"],
        weight_dtype=(engine_overrides or {}).get("weight_dtype", ""))
    t_build = time.perf_counter() - t
    mem_build = peak_bytes()
    log(f"build: {t_build!r} s, device peak {mem_build} B "
        f"(kernel_impl={cfg.kernel_impl} attn_impl={cfg.attn_impl})")
    t = time.perf_counter()
    engine = build.build_engine(cfg, m, params, seed,
                                **(engine_overrides or {}))
    del params
    plens = traffic.prompt_lengths(mix)
    olens = traffic.output_lengths(mix)
    warm = build.warm_up(engine, plens, olens)
    t_warm = time.perf_counter() - t
    log(f"warm-up: {t_warm!r} s, {warm}")
    if mutate_engine is not None:
        mutate_engine(engine)

    records: Dict[int, Dict[str, Any]] = {}
    on_terminal = engine.sched.on_terminal

    def record_terminal(req):
        records[req.rid] = {"rid": req.rid, "status": req.status,
                            "submit": req.submit_time,
                            "admit": req.admit_time,
                            "first": req.first_token_time,
                            "end": time.perf_counter(),
                            "preemptions": req.preemptions}
        if on_terminal is not None:
            on_terminal(req)
    engine.sched.on_terminal = record_terminal

    handle = start_in_thread(engine, ServerConfig(port=0))
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "lib", "client.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env={k: v for k, v in os.environ.items()
             if not k.startswith(("JAX", "XLA", "TPU", "LIBTPU"))})
    try:
        child.stdin.write(json.dumps({
            "port": handle.port, "mix": mix_file, "seed": seed,
            "vocab": m["vocab_size"], "seconds": seconds}) + "\n")
        child.stdin.flush()
        if child.stdout.readline().strip() != "READY":
            raise RuntimeError("load client failed to start")
        warm_in = float(mix.get("warm_in_s", 0.0))
        t_go = time.perf_counter()
        child.stdin.write("GO\n")
        child.stdin.flush()

        def snap():
            with engine._elock:
                st = engine.stats
                return {"occupancy": len(st["slot_occupancy"]),
                        "preemptions": st["preemptions"],
                        "decode_steps": st["decode_steps"],
                        "prefills": st["prefills"],
                        "waiting": len(engine.sched.waiting),
                        "compiles": counter.snapshot()}
        time.sleep(max(0.0, t_go + warm_in - time.perf_counter()))
        s0 = snap()
        traced_decodes = None
        if trace:
            tlen = min(TRACE_SECONDS, seconds / 2)
            time.sleep(max(0.0, (seconds - tlen) / 2))
            shutil.rmtree(os.path.join(TRACE_DIR, cell_name),
                          ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            d0 = engine.stats["decode_steps"]
            jax.profiler.start_trace(os.path.join(TRACE_DIR, cell_name),
                                     profiler_options=opts)
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
                time.sleep(tlen)
            jax.profiler.stop_trace()
            traced_decodes = engine.stats["decode_steps"] - d0
        time.sleep(max(0.0, t_go + warm_in + seconds - time.perf_counter()))
        s1 = snap()
        out, _ = child.communicate(timeout=180)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        if mix_file != c["mix_file"]:
            os.remove(mix_file)
    client = json.loads(out.strip().splitlines()[-1])
    handle.request_drain()
    handle.wait_closed(timeout=120.0)
    conserved = handle.server.conservation_ok
    t0, t1 = client["t0"], client["t1"]
    setup_s = t0 - T_PROCESS
    occ = engine.stats["slot_occupancy"][s0["occupancy"]:s1["occupancy"]]
    window = {k: s1[k] - s0[k] for k in ("preemptions", "decode_steps",
                                         "prefills")}
    window["waiting"] = (s0["waiting"], s1["waiting"])
    window["compiles"] = s1["compiles"][0] - s0["compiles"][0]
    window["traces"] = s1["compiles"][1] - s0["compiles"][1]
    log(f"window: {window}; generator lateness max "
        f"{client['late_max_s']!r} s mean {client['late_mean_s']!r} s; "
        f"client threads left {client['alive_threads']}")
    log(f"compiles inside the window: {window['compiles']} "
        f"(retraces {window['traces']})")
    counter.close()
    mem_peak = peak_bytes()

    ctx = {"model": m, "peaks": peaks, "setup_s": setup_s,
           "client": client, "t0": t0, "t1": t1, "window": window,
           "occupancy": occ, "n_slots": m["engine"]["n_slots"],
           "engine_records": records, "trace": None,
           "traced_decodes": traced_decodes}
    breakdown = None
    device = dict(dev, memory_peak_bytes=mem_peak)
    if trace:
        path = trace_lib.find_xplane(os.path.join(TRACE_DIR, cell_name))
        red = trace_lib.reduce(trace_lib.load(path))
        trace_lib.check_decode(red)
        ctx["trace"] = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        paired = sum(e["dispatch"] is not None
                     for e in red["execs"]["decode_sample"])
        log(f"trace: {path}; whole executions "
            f"{ {k: len(v) for k, v in red['programs'].items()} }; "
            f"decode executions paired with a dispatch span {paired}; "
            f"decode steps counted while tracing {traced_decodes}")

    # free the program's state before the reference runs
    for leaf in jax.tree_util.tree_leaves((engine.params, engine.pool.cache)):
        leaf.delete()
    del engine, handle
    gc.collect()

    t = time.perf_counter()
    plan = traffic.plan(mix, seed, m["vocab_size"],
                        traffic.window_requests(mix, seconds))
    sample = sample_requests(client["requests"], seed)
    short = sum(1 for r in sample if len(r["tokens"]) != r["max_tokens"])
    limits = _limits(cell_name) if limits is None else limits
    numbers: Dict[str, Optional[float]] = {k: None for k in limits}
    if sample:
        cmp = compare(m, seed, sample, plan)
        log(f"reference: {cmp['n_requests']} requests, {cmp['n_tokens']} "
            f"served tokens, padded to {cmp['pad']}, "
            f"{time.perf_counter() - t!r} s; per-request widest gaps "
            f"{cmp['per_request']}; all readings {cmp['numbers']}")
        numbers.update(cmp["numbers"])
    checks = {k: {"value": numbers[k], "limit": v}
              for k, v in limits.items()}
    checks["short_answers"] = {"value": short, "limit": 0}
    checks["drain_conserved"] = {"value": int(bool(conserved)), "limit": 1}
    correct = (bool(sample) and bool(limits) and bool(conserved)
               and all(ch["value"] is not None and ch["value"] <= ch["limit"]
                       for k, ch in checks.items() if k != "drain_conserved"))

    chosen = c["per_layer"] if trace else c["end_to_end"]
    metrics = {}
    for spec_m in chosen:
        v = read_metric(spec_m["name"], ctx)
        if v is not None:
            metrics[spec_m["name"]] = {"value": v, "unit": spec_m["unit"]}
    att, fail = stats.attempted_failed(client["requests"], t0, t1)
    res = {"correct": correct, "attempted": att, "failed": fail,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["readings"] = numbers
    res["checks"] = checks
    return res


def _limits(cell_name: str) -> Dict[str, float]:
    """The cell's limits (``limits/<cell>.json``): each number compared
    with the reference, and the most it may read."""
    path = os.path.join(BENCH, "limits", cell_name + ".json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    a = p.parse_args(argv)
    res = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    print(json.dumps(res), flush=True)
    # the checks again as the very last stderr lines
    for name, ch in res["checks"].items():
        log(f"check {name}: {ch['value']!r} (limit {ch['limit']!r})")


if __name__ == "__main__":
    main()
