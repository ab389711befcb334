"""Required-work counts of the dense family against hand counts for one
layer of each configuration, from one dispatch span's args."""

import json
import os

import pytest

from families import dense_decoder
from lib import roofline

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")
WORK = dense_decoder.WORK


def cfg(name, layers=1):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        m = json.load(f)
    return dict(m, num_hidden_layers=layers)


def args(rows, ctx_tokens):
    return {"step": 7, "rows": rows, "ctx_tokens": ctx_tokens}


def test_qwen_one_layer_by_hand():
    m = cfg("qwen1.5-4b")
    d, f, v = 2560, 6912, 151936
    proj = 4 * d * d + 3 * d * f                  # MHA: q, k, v, o = d x d
    kept = 0.25 * (proj + v * d)
    flops, byts = WORK["bcr_spmm"](m, args(3, 0))
    assert flops == pytest.approx(2 * 3 * kept)
    act = 3 * 2 * (4 * 2 * d + 2 * (f + d) + (d + f) + (v + d))
    assert byts == pytest.approx(2 * kept + act)
    # attention: 20 heads x 128, context 100 and 50 over one layer
    fa, ba = WORK["paged_attention"](m, args(2, 150))
    assert fa == pytest.approx(4 * 150 * 20 * 128)
    assert ba == pytest.approx(150 * 2 * 20 * 128 * 2 + 2 * 2 * 20 * 128 * 2)


def test_pixtral_one_layer_by_hand():
    m = cfg("pixtral-12b-l20")
    d, f, v, hq, hkv = 5120, 14336, 131072, 4096, 1024
    proj = hq * d + 2 * hkv * d + d * hq + 3 * d * f
    flops, _ = WORK["bcr_spmm"](m, args(1, 10))
    assert flops == pytest.approx(2 * 0.25 * (proj + v * d))
    fa, ba = WORK["paged_attention"](m, args(1, 10))
    assert fa == pytest.approx(4 * 10 * 32 * 128)
    assert ba == pytest.approx(10 * 2 * 8 * 128 * 2 + 2 * 32 * 128 * 2)


def test_whole_step_is_the_sum_and_bound_is_memory_at_decode():
    m = cfg("qwen1.5-4b", layers=40)
    a = args(4, 4000)
    f1, b1 = WORK["bcr_spmm"](m, a)
    f2, b2 = WORK["paged_attention"](m, a)
    f, b = WORK["decode_step"](m, a)
    assert f == pytest.approx(f1 + f2)
    assert b == pytest.approx(b1 + b2 + 4 * 2560 * 2)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = roofline.ideal_s(f, b, peaks)
    assert bound == "memory" and t == pytest.approx(b / 819e9)
