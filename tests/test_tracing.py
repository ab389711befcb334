"""Host spans and KV-page gauges of the serving engine and its HTTP front
end, read back from real ``jax.profiler`` traces (CPU, smoke config, paged
pool): the span tree and its order, the decode dispatch's ``rows`` and
``ctx_tokens`` against the pool's live lengths and its attention-kernel
step counts against a recount from them, the three page gauges
summing to the pool's pages through admission, decode, release,
preemption and a prefix-cache hit, the same gauges on ``/metrics``, and
greedy tokens unchanged by an active profiler session."""

import glob
import re
import threading
from collections import namedtuple

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.kernels.paged_decode_attention import attention_blocks
from repro.models.api import model_fns
from repro.serving import EngineConfig, InferenceEngine, OracleDraft
from repro.serving.server import (ServerConfig, http_request,
                                  start_in_thread, stream_completion)

Span = namedtuple("Span", "name a b args thread")

PAGE = 8
GEN = 6
GAUGES = ("kv_pages_in_use", "kv_pages_reserved", "kv_pages_free")
STEP_ARGS = {"step", "live", "waiting"} | set(GAUGES)
#: a step's children, in order: admission, its prefill groups, then a
#: decode (dispatch, wait, commit) or a speculative step
STEP_SHAPE = re.compile(r"admit (prefill )*((dispatch wait commit )|(spec ))?")
SHORT = {"engine.admit": "admit", "engine.prefill": "prefill",
         "engine.decode.dispatch": "dispatch",
         "engine.decode.wait": "wait", "engine.commit": "commit",
         "engine.spec": "spec"}

#: scenario -> (EngineConfig overrides, what the run must have exercised)
SCENARIOS = {
    "decode": ({}, ()),
    "prefix": ({"prefix_cache": True}, ("prefix_hit_tokens",)),
    "preempt": ({"kv_pages": 8, "preempt_after_stalls": 1},
                ("preemptions", "page_stalls")),
    # an oracle drafter with nothing to propose: 1-token verify steps
    "spec": ({"spec_k": 2}, ("spec_steps",)),
}


@pytest.fixture(scope="module")
def llama():
    cfg = get_smoke_config("llama3.2-1b")
    params = model_fns(cfg).init_params(jax.random.PRNGKey(0))
    return cfg, params


def make_engine(llama, **kw):
    cfg, params = llama
    ec = dict(n_slots=3, capacity=64, page_size=PAGE, plan_packed=False)
    ec.update(kw)
    drafter = OracleDraft() if ec.get("spec_k") else None
    return InferenceEngine(cfg, params, EngineConfig(**ec), drafter=drafter)


def prompts(cfg, n=5, seed=3):
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab_size, size=20).astype(np.int32)
    out = [shared]
    for _ in range(n - 2):
        out.append(rng.integers(1, cfg.vocab_size,
                                size=int(rng.integers(9, 17)))
                   .astype(np.int32))
    out.append(shared.copy())       # a prefix-cache hit when sharing is on
    return out


def traced(log_dir, fn):
    """Run ``fn`` under a profiler session; return its result and the
    program's host spans, in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(log_dir / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("engine.", "server.")):
                    spans.append(Span(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats), line.name))
    return out, sorted(spans, key=lambda s: (s.a, -s.b))


def drive(eng, ps):
    """Submit the prompts (the last one after the first finished, so a
    prefix cache holds it) and step to the end. Returns the tokens by
    prompt, the pool's lengths at each decode call, and the gauges
    mirrored into ``stats`` after each step."""
    lens_at_decode, mirrored = [], []
    decode = eng._decode

    def logged(*a, **kw):
        lens_at_decode.append(np.array(eng.pool.lens))
        return decode(*a, **kw)
    eng._decode = logged
    rids = [eng.submit(p, max_new_tokens=GEN) for p in ps[:-1]]
    done = {}
    while len(done) < len(ps):
        for r in eng.step():
            done[r.rid] = list(r.generated)
        mirrored.append({k: eng.stats[k] for k in GAUGES})
        eng.pool.check_consistency()
        if rids[0] in done and len(rids) < len(ps):
            rids.append(eng.submit(ps[-1], max_new_tokens=GEN))
    eng._decode = decode
    return ([done[r] for r in rids], lens_at_decode, mirrored,
            {r: len(p) for r, p in zip(rids, ps)})


@pytest.fixture(scope="module")
def runs(llama, tmp_path_factory):
    """Each scenario once under a profiler session and once without."""
    cfg = llama[0]
    out = {}
    for name, (kw, _) in SCENARIOS.items():
        eng = make_engine(llama, **kw)
        res, spans = traced(tmp_path_factory.mktemp(name),
                            lambda: drive(eng, prompts(cfg)))
        plain = drive(make_engine(llama, **kw), prompts(cfg))[0]
        out[name] = {"engine": eng, "tokens": res[0], "lens": res[1],
                     "mirrored": res[2], "prompt_len": res[3],
                     "spans": spans, "plain": plain}
    return out


def steps_of(spans):
    """[(engine.step span, its child spans in order)]."""
    steps = [s for s in spans if s.name == "engine.step"]
    kids = [s for s in spans if s.name in SHORT]
    tree = [(st, [k for k in kids if st.a <= k.a and k.b <= st.b])
            for st in steps]
    assert sum(len(k) for _, k in tree) == len(kids), \
        "an engine span lies outside every engine.step"
    return tree


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_span_tree_and_order(runs, scenario):
    run = runs[scenario]
    tree = steps_of(run["spans"])
    assert len(tree) >= GEN
    assert [st.args["step"] for st, _ in tree] == list(
        range(tree[0][0].args["step"], tree[0][0].args["step"] + len(tree)))
    first = tree[0][0].args
    assert first["live"] + first["waiting"] == len(run["prompt_len"]) - 1
    for st, kids in tree:
        assert set(st.args) == STEP_ARGS
        assert {k.thread for k in kids} <= {st.thread}
        shape = "".join(SHORT[k.name] + " " for k in kids)
        assert STEP_SHAPE.fullmatch(shape), shape
        for k0, k1 in zip(kids, kids[1:]):
            assert k0.b <= k1.a, (k0.name, k1.name)
        admit = kids[0]
        assert set(admit.args) == {"admitted", "stalled"}
        prefills = [k for k in kids if k.name == "engine.prefill"]
        assert sum(p.args["rows"] for p in prefills) == \
            admit.args["admitted"]
        for p in prefills:
            rids = [int(r) for r in str(p.args["rids"]).split(";")]
            assert len(rids) == p.args["rows"]
            # a prefix hit prefills only its suffix
            assert p.args["width"] >= (1 if scenario == "prefix" else max(
                run["prompt_len"][r] for r in rids))
    assert any(k.name == "engine.prefill" for _, ks in tree for k in ks)
    assert any(ks[0].args["stalled"] for _, ks in tree) == \
        (scenario == "preempt")
    st = run["engine"].stats
    for key in SCENARIOS[scenario][1]:
        assert st[key] > 0, key


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_decode_dispatch_args_are_the_live_lengths(runs, scenario):
    run = runs[scenario]
    disp = [s for s in run["spans"] if s.name == "engine.decode.dispatch"]
    assert len(disp) == len(run["lens"])
    assert (len(disp) == 0) == (scenario == "spec")
    live_at = {st.args["step"]: st.args["live"]
               for st, _ in steps_of(run["spans"])}
    for span, lens in zip(disp, run["lens"]):
        live = lens[lens > 0]
        assert span.args["rows"] == live.size
        # a re-admitted request can finish on its prefill's token
        assert span.args["rows"] <= live_at[span.args["step"]]
        assert span.args["ctx_tokens"] == int((live + 1).sum())
        assert (span.args["attn_grid_steps"],
                span.args["attn_live_steps"]) == attn_steps(run["engine"],
                                                            lens)


def attn_steps(eng, lens):
    """One layer's paged-attention grid steps and live steps, recounted
    from the pool's lengths: every slot attends over ``len + 1``
    positions of a table as wide as the pow2 bucket of the longest live
    slot's pages, walked ``pages`` columns and ``heads`` KV heads a step."""
    cfg = eng.cfg
    cdiv = lambda a, b: -(-a // b)
    need = cdiv(int(lens.max()) + 1, PAGE)
    width = min(1 << (need - 1).bit_length(), eng.pool.max_pages)
    pages, heads = attention_blocks(
        PAGE, cfg.num_kv_heads, cfg.head_dim, cfg.c_dtype.itemsize,
        cfg.num_heads // cfg.num_kv_heads, width)
    groups = cfg.num_kv_heads // heads
    live = sum(cdiv(cdiv(int(n) + 1, PAGE), pages) for n in lens)
    return len(lens) * groups * cdiv(width, pages), groups * live


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_page_gauges_cover_the_pool(runs, scenario):
    run = runs[scenario]
    pool = run["engine"].pool
    total = pool.n_pages - 1
    traced_gauges = [tuple(st.args[g] for g in GAUGES)
                     for st, _ in steps_of(run["spans"])]
    for g in traced_gauges + [tuple(m[k] for k in GAUGES)
                              for m in run["mirrored"]]:
        assert sum(g) == total and min(g) >= 0, g
    # pages were held and reserved mid-run, and all came back at the end
    assert max(g[0] for g in traced_gauges) > 0
    assert max(g[1] for g in traced_gauges) > 0
    end = run["mirrored"][-1]
    assert end["kv_pages_reserved"] == 0
    assert end["kv_pages_in_use"] == 0
    assert pool.page_gauges() == end


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_tokens_unchanged_by_profiler(runs, scenario):
    run = runs[scenario]
    assert run["tokens"] == run["plain"]
    assert all(len(t) == GEN for t in run["tokens"])


def test_served_run_spans_and_metrics(llama, tmp_path):
    """A short run through the HTTP front end: the pump's and the idle
    wait's spans sit beside ``engine.step`` on the engine thread, the
    pump's ``tokens`` count every token the clients received, and
    ``/metrics`` carries the page gauges."""
    cfg = llama[0]
    eng = make_engine(llama)
    h = start_in_thread(eng, ServerConfig(port=0), warmup_lens=(16,))
    try:
        got = {}

        def client(i, p):
            got[i] = stream_completion("127.0.0.1", h.port, {
                "prompt": [int(t) for t in p], "max_tokens": GEN,
                "stream": True}).tokens

        def serve():
            ts = [threading.Thread(target=client, args=(i, p))
                  for i, p in enumerate(prompts(cfg, n=4))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(60)
            return http_request("127.0.0.1", h.port, "GET", "/metrics")
        (code, _, body), spans = traced(tmp_path, serve)
    finally:
        h.request_drain()
        h.wait_closed(60)
    assert code == 200
    assert len(got) == 4 and all(len(t) == GEN for t in got.values())
    pumps = [s for s in spans if s.name == "server.pump"]
    steps = [s for s in spans if s.name == "engine.step"]
    idle = [s for s in spans if s.name == "server.idle"]
    assert pumps and steps and idle
    assert {s.thread for s in pumps + steps + idle} == {steps[0].thread}
    assert all(set(p.args) == {"streams", "tokens"} for p in pumps)
    assert sum(p.args["tokens"] for p in pumps) == 4 * GEN
    assert max(p.args["streams"] for p in pumps) == 4
    for p in pumps + idle:      # siblings of the steps, never inside one
        assert not any(st.a < p.a < st.b for st in steps)
    engine = body["engine"]
    assert sum(engine[g] for g in GAUGES) == eng.pool.n_pages - 1
    assert "tokens_generated" not in engine
    assert "deferred_admissions" not in engine
