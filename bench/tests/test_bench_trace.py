"""The trace reducer's arithmetic on a hand-made trace: decode executions
and a prefill on one TPU plane, with kernel ops inside them, an overlap,
idle gaps, and an execution cut by each edge of the traced window."""

import pytest

from lib import roofline, trace
from metrics import decode_step_ms


def raw(window=(0.0, 4.0)):
    mods = [("jit_decode_sample(1)", -0.5, 0.25),       # cut by the start
            ("jit_decode_sample(1)", 0.5, 1.5),
            ("jit_prefill_sample(2)", 2.0, 2.5),
            ("jit_decode_sample(1)", 3.0, 3.5),
            ("jit_decode_sample(1)", 3.75, 4.5)]        # cut by the stop
    ops = [("bcr_spmm.0", -0.5, 0.25),
           ("bcr_spmm.1", 0.5, 0.75), ("paged_attention", 0.75, 1.25),
           ("fusion.3", 1.2, 1.5),                      # overlaps by 0.05
           ("bcr_spmm_grouped.2", 2.0, 2.5),            # in the prefill
           ("bcr_spmm_grouped.4", 3.0, 3.25), ("paged_attention", 3.25, 3.5),
           ("paged_attention", 3.75, 4.5)]
    host = [(trace.WINDOW,) + window,
            ("bench:decode:6", -0.6, -0.55), ("bench:decode:7", 0.4, 0.45),
            ("bench:engine.step", 1.5, 1.95),
            ("bench:decode:8", 2.7, 2.8), ("bench:decode:9", 3.7, 3.72)]
    return {"devices": {"/device:TPU:0": {"modules": mods, "ops": ops}},
            "host": host}


def test_whole_executions_only():
    red = trace.reduce(raw())
    assert red["window_s"] == 4.0
    assert red["busy_s"] == pytest.approx(0.25 + 1.0 + 0.5 + 0.5 + 0.25)
    assert red["programs"]["decode_sample"] == pytest.approx([1.0, 0.5])
    assert red["programs"]["prefill_sample"] == pytest.approx([0.5])
    d = red["execs"]["decode_sample"]
    assert [e["call"] for e in d] == [7, 8]
    assert d[0]["kernels"] == pytest.approx({"bcr_spmm": 0.25,
                                             "paged_attention": 0.5})
    assert d[1]["kernels"] == pytest.approx({"bcr_spmm_grouped": 0.25,
                                             "paged_attention": 0.25})
    assert red["execs"]["prefill_sample"][0]["kernels"] == \
        pytest.approx({"bcr_spmm_grouped": 0.5})
    trace.check_decode(red)


def test_cut_executions_move_no_share():
    """Moving the window's edges across the cut executions changes the
    busy time, never the step time or a share."""
    ctx = {"model": None, "peaks": {"bf16_flops": 1.0,
                                    "hbm_bytes_per_s": 1.0},
           "decode_log": {k: [10] for k in range(6, 10)}}
    reads = []
    for w in ((0.0, 4.0), (-0.1, 4.1), (0.2, 3.8)):
        ctx["trace"] = trace.reduce(raw(w))
        reads.append((decode_step_ms.read(ctx),
                      roofline.share(ctx, lambda m, c: (0.0, 0.01),
                                     roofline.kernel_seconds("bcr_spmm")),
                      roofline.kernel_seconds("paged_attention")(
                          ctx["trace"]["execs"]["decode_sample"][0])))
    assert reads[0][0] == pytest.approx(750.0)
    assert reads[1:] == [reads[0], reads[0]]


def test_idle_gaps_labelled_and_sorted():
    red = trace.reduce(raw())
    gaps = red["idle_gaps"]
    assert [g for _, g in gaps] == pytest.approx([0.5, 0.5, 0.25, 0.25])
    labels = {n for n, _ in gaps}
    assert "decode_sample -> prefill_sample [engine.step]" in labels
    assert "prefill_sample -> decode_sample [decode]" in labels
    assert red["device_ops"][0][0] in ("paged_attention", "bcr_spmm_grouped")


def test_window_must_be_marked_and_overlap():
    r = raw()
    r["host"] = r["host"][1:]
    with pytest.raises(ValueError, match="window"):
        trace.reduce(r)
    with pytest.raises(ValueError, match="overlaps"):
        trace.reduce(raw((10.0, 14.0)))


def test_check_decode_fails_loudly():
    r = raw()
    for ev in list(r["devices"]["/device:TPU:0"]["ops"]):
        if trace.kernel_of(ev[0]):
            r["devices"]["/device:TPU:0"]["ops"].remove(ev)
    r["devices"]["/device:TPU:0"]["ops"].append(("fusion.9", 0.5, 1.0))
    with pytest.raises(RuntimeError, match="no .* event inside"):
        trace.check_decode(trace.reduce(r))
    r = raw()
    r["host"] = r["host"][:1]
    with pytest.raises(RuntimeError, match="matched"):
        trace.check_decode(trace.reduce(r))


def test_names():
    assert trace.kernel_of("bcr_spmm_grouped.3") == "bcr_spmm_grouped"
    assert trace.kernel_of("bcr_spmm.12") == "bcr_spmm"
    assert trace.kernel_of("paged_attention") == "paged_attention"
    assert trace.kernel_of("fusion.4") is None
    assert trace.program_of("jit_decode_sample(123)") == "decode_sample"
    assert trace.op_family("fusion.12") == "fusion"
