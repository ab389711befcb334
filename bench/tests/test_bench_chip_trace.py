"""The trace reducer on a trace recorded on a TPU v5e by ``bench/run.py
--trace 1`` in the cell ``pixtral-12b-l20.batch_gen``, trimmed to the two
lines of the device plane that the reducer reads and to the harness's host
spans, over the first 0.75 s of its window (three whole decode steps and a
fourth cut by the window's end; the ``bench:window`` span is shortened to
that stretch). On the chip an op event is named by its HLO instruction's
text, ``%bcr_spmm.14 = bf16[...] custom-call(...)``."""

import os

import pytest

from lib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "batch_gen.xplane.pb.gz")


@pytest.fixture(scope="module")
def red():
    return trace.reduce(trace.load(DATA))


def test_chip_trace_names_are_found(red):
    ex = red["execs"]["decode_sample"]
    assert len(ex) == 3
    for e in ex:
        assert set(e["kernels"]) == set(trace.KERNELS)
        # the program recorded no spans yet, so nothing pairs a decode
        # (``test_bench_spans.py`` pairs them on a later trace)
        assert e["dispatch"] is None
        assert 0.0 < sum(e["kernels"].values()) < e["s"]


def test_chip_trace_numbers(red):
    assert red["window_s"] == pytest.approx(0.7524, abs=1e-3)
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert red["programs"]["decode_sample"] == pytest.approx(
        [0.24366, 0.24381, 0.24394], abs=2e-5)
    fams = dict(red["device_ops"])
    assert {"bcr_spmm", "bcr_spmm_grouped", "paged_attention"} <= set(fams)
    assert all(not n.startswith("%") and " " not in n for n in fams)


def test_instruction_names():
    ev = ("%bcr_spmm_grouped.22 = bf16[2,24,1024]{2,1,0} custom-call("
          "bf16[24,5120]{1,0} %fusion.76)")
    assert trace.instruction(ev) == "bcr_spmm_grouped.22"
    assert trace.kernel_of(ev) == "bcr_spmm_grouped"
    assert trace.op_family("%fusion.82 = f32[8]{0} fusion(bf16[8] %a)") \
        == "fusion"
    assert trace.kernel_of("%paged_attention.3 = bf16[8] custom-call()") \
        == "paged_attention"
