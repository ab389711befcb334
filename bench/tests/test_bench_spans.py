"""The program-span readers (``host_gap_ms``, ``pump_ms``,
``kv_reserved_share``) and ``lib/spans.py`` on hand-made spans (steps cut
by each edge of the traced window, a step that decodes nothing, a trace
from another run, a program that records no span) and on a trimmed chip
trace."""

import gzip
import os

import pytest

from lib import spans, trace
from lib.spans import Span
from metrics import host_gap_ms, kv_reserved_share, pump_ms

READERS = (host_gap_ms, pump_ms, kv_reserved_share)


def step(n, a, b, dispatch_end=None, wait_end=None, gauges=(0, 0, 0)):
    """An engine.step span [a, b] with its children: admission, then (if
    ``dispatch_end``) a decode whose dispatch ends there and whose wait
    ends at ``wait_end``, then the commit."""
    args = dict(step=n, live=3, waiting=1,
                **dict(zip(spans.GAUGES, gauges)))
    out = [Span("engine.step", a, b, args),
           Span("engine.admit", a, a + 0.005, {"admitted": 0,
                                                "stalled": 0})]
    if dispatch_end is not None:
        out += [Span("engine.decode.dispatch", a + 0.005, dispatch_end,
                     {"step": n, "rows": 3, "ctx_tokens": 99}),
                Span("engine.decode.wait", dispatch_end, wait_end, {}),
                Span("engine.commit", wait_end, b, {})]
    return out


def hand_run(window=(0.0, 1.0), calls=(1, 2, 3, 5)):
    ss = (step(1, -0.05, 0.15, 0.0, 0.14, (9, 9, 2))       # cut by start
          + step(2, 0.2, 0.45, 0.22, 0.40, (10, 5, 5))
          + step(3, 0.5, 0.75, 0.52, 0.70, (12, 3, 5))
          + step(4, 0.76, 0.78, gauges=(0, 20, 0))           # no decode
          + step(5, 0.8, 1.05, 0.83, 1.0, (1, 1, 18))        # cut by stop
          + [Span("server.pump", 0.45, 0.46, {"streams": 3, "tokens": 3}),
             Span("server.pump", 0.755, 0.758, {"streams": 3,
                                                "tokens": 0}),
             Span("server.pump", 0.99, 1.01, {"streams": 3, "tokens": 3}),
             Span("server.idle", 0.46, 0.5, {})])
    return {"path": "hand-made", "window": window,
            "spans": sorted(ss, key=lambda s: (s.a, -s.b)),
            "calls": list(calls)}


def ctx_for(run, decode_log=None):
    t0, t1 = run["window"]
    log = {k: [10] for k in run["calls"]} if decode_log is None \
        else decode_log
    return {"trace": {"window_s": t1 - t0}, "decode_log": log}


@pytest.fixture
def serve(monkeypatch):
    """Hand ``run`` to the readers as the newest trace."""
    def put(run):
        monkeypatch.setattr(spans, "newest", lambda: run["path"])
        monkeypatch.setattr(spans, "load", lambda path: dict(run))
        return run
    return put


def read_all(ctx):
    return [r.read(ctx) for r in READERS]


def test_readers_on_hand_made_spans(serve, capsys):
    run = serve(hand_run())
    gap, pump, share = read_all(ctx_for(run))
    # only steps 2 and 3 are whole decode steps next to each other
    assert gap == pytest.approx(1e3 * (0.52 - 0.40))
    assert pump == pytest.approx(1e3 * (0.010 + 0.003) / 2)
    assert share == pytest.approx(100.0 * (5 / 20 + 3 / 20) / 2)
    err = capsys.readouterr().err
    assert "spans: engine.decode.wait" in err and "spans: 2 decode" in err


@pytest.mark.parametrize("window", [(-0.01, 1.0), (0.0, 1.005),
                                    (0.16, 0.79)])
def test_cut_steps_move_nothing(serve, window):
    """Moving the window's edges across the cut steps and pumps leaves
    every reading as it was."""
    whole = serve(hand_run())
    want = read_all(ctx_for(whole))
    run = serve(dict(hand_run(window), path=f"hand-made {window}"))
    got = read_all(ctx_for(run))
    assert got == [pytest.approx(v) for v in want]


def test_trace_from_another_run_is_refused(serve):
    run = serve(hand_run())
    ctx = ctx_for(run, decode_log={k: [10] for k in (1, 2, 3)})
    for r in READERS:
        with pytest.raises(ValueError, match="decode log"):
            r.read(ctx)
    ctx = ctx_for(run)
    ctx["trace"]["window_s"] += 2e-6
    with pytest.raises(ValueError, match="window"):
        host_gap_ms.read(ctx)
    ctx["trace"]["window_s"] -= 1.5e-6          # within 1 us: its own
    assert host_gap_ms.read(ctx) is not None


def test_program_without_spans_reads_nothing(serve):
    run = serve(dict(hand_run(), spans=[]))
    assert read_all(ctx_for(run)) == [None, None, None]
    assert read_all({"trace": None, "decode_log": {}}) == [None] * 3


def test_spans_without_engine_step_fail(serve):
    run = hand_run()
    run["spans"] = [s for s in run["spans"] if s.name != "engine.step"]
    serve(run)
    with pytest.raises(ValueError, match="engine.step"):
        kv_reserved_share.read(ctx_for(run))


def test_traced_run_without_a_trace_fails(monkeypatch, tmp_path):
    monkeypatch.setattr(spans, "TRACES", str(tmp_path))
    with pytest.raises(FileNotFoundError):
        pump_ms.read(ctx_for(hand_run()))


def test_steps_group_children_by_containment():
    st = spans.steps(hand_run())
    assert [s["step"] for s in st] == [2, 3, 4]
    assert sorted(st[0]["kids"]) == sorted(
        ["engine.admit", "engine.decode.dispatch", "engine.decode.wait",
         "engine.commit"])
    assert list(st[2]["kids"]) == ["engine.admit"]


# A trace recorded on a TPU v5e by ``bench/run.py --trace 1`` in the cell
# ``pixtral-12b-l20.batch_gen`` (seed 3130000001), trimmed to the device
# plane's XLA Modules and XLA Ops lines (no op stats), the harness's
# ``bench:`` spans and the program's spans with their args, over the first
# 0.75 s of its window (``bench:window`` shortened to that stretch): steps
# 93 and 94 whole, 95 cut by the window's end.
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "batch_gen_spans.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip():
    run = spans.load(DATA)
    red = trace.reduce(trace.load(DATA))
    return run, red


def chip_ctx(chip):
    run, red = chip
    return {"trace": red, "decode_log": {k: [1] for k in run["calls"]}}


def test_chip_trace_spans(chip):
    run, red = chip
    trace.check_decode(red)
    assert run["calls"] == [81, 82, 83]
    st = spans.steps(run)
    assert [s["step"] for s in st] == [93, 94]
    for s in st:
        assert list(s["kids"]) == ["engine.admit", "engine.decode.dispatch",
                                   "engine.decode.wait", "engine.commit"]
        args = s["span"].args
        assert args["live"] == 21 and args["waiting"] == 15
        assert sum(args[g] for g in spans.GAUGES) == 1200
        assert s["kids"][spans.DISPATCH][0].args["rows"] == 21
    # each live row's context grows by one token a step
    ctx = [s["kids"][spans.DISPATCH][0].args["ctx_tokens"] for s in st]
    assert ctx[1] - ctx[0] == 21


def test_chip_trace_readers(chip, monkeypatch):
    monkeypatch.setattr(spans, "newest", lambda: DATA)
    ctx = chip_ctx(chip)
    assert host_gap_ms.read(ctx) == pytest.approx(5.00794, abs=1e-4)
    assert pump_ms.read(ctx) == pytest.approx(1.07049, abs=1e-4)
    assert kv_reserved_share.read(ctx) == pytest.approx(
        100.0 * (505 + 504) / 2400)


def test_chip_trace_from_another_run_is_refused(chip, monkeypatch):
    monkeypatch.setattr(spans, "newest", lambda: DATA)
    ctx = chip_ctx(chip)
    del ctx["decode_log"][83]
    with pytest.raises(ValueError, match="decode log"):
        pump_ms.read(ctx)
    ctx = chip_ctx(chip)
    ctx["trace"] = dict(ctx["trace"], window_s=ctx["trace"]["window_s"]
                        + 2e-6)
    with pytest.raises(ValueError, match="window"):
        pump_ms.read(ctx)


def test_for_run_takes_the_newest_trace(chip, monkeypatch, tmp_path):
    with gzip.open(DATA, "rb") as f:
        raw = f.read()
    paths = []
    for cell, mtime in (("old", 1e9), ("new", 2e9)):
        d = tmp_path / cell / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        paths.append(d / "host.xplane.pb")
        paths[-1].write_bytes(raw if cell == "new" else b"")
        os.utime(paths[-1], (mtime, mtime))
    monkeypatch.setattr(spans, "TRACES", str(tmp_path))
    ctx = chip_ctx(chip)
    assert spans.for_run(ctx)["path"] == str(paths[1])
    assert ctx[spans.CTX_KEY]["path"] == str(paths[1])


def test_trace_of_a_program_without_spans_reads_nothing(monkeypatch):
    """The older chip trace (``batch_gen.xplane.pb.gz``) holds the
    harness's spans only, as a program without spans records."""
    old = os.path.join(os.path.dirname(DATA), "batch_gen.xplane.pb.gz")
    monkeypatch.setattr(spans, "newest", lambda: old)
    run = spans.load(old)
    ctx = {"trace": trace.reduce(trace.load(old)),
           "decode_log": {k: [1] for k in run["calls"]}}
    assert run["spans"] == [] and run["calls"]
    assert read_all(ctx) == [None, None, None]
