"""The traffic generator: every seed serves the same sizes, in another
order, or in one fixed order where the mix sets ``order_seed``."""

import collections
import json
import os

from lib import traffic
from small import SMALL_OPEN

MIXES = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def load(name):
    return traffic.load_mix(os.path.join(MIXES, name + ".json"))


def test_every_block_holds_the_same_sizes_under_every_seed():
    for name in ("doc_qa", "batch_gen"):
        mix = load(name)
        b = mix["strata"]
        want = None
        for seed in (1, 2, 2**40 + 7):
            reqs = traffic.plan(mix, seed, 1000, 4 * b)
            for k in range(4):
                blk = reqs[k * b:(k + 1) * b]
                sizes = (collections.Counter(r["prompt_len"] for r in blk),
                         collections.Counter(r["max_tokens"] for r in blk))
                want = want or sizes
                assert sizes == want


def test_same_seed_same_plan_and_tokens():
    mix = SMALL_OPEN
    a = traffic.plan(mix, 99, 5000, 40)
    b = traffic.plan(mix, 99, 5000, 40)
    assert a == b
    assert traffic.prompt_tokens(a[3], 5000) == traffic.prompt_tokens(
        b[3], 5000)
    assert a != traffic.plan(mix, 100, 5000, 40)


def test_lengths_within_the_mix_limits():
    for name in ("doc_qa", "batch_gen"):
        mix = load(name)
        for r in traffic.plan(mix, 5, 1000, 64):
            assert mix["prompt"]["min"] <= r["prompt_len"] <= mix["prompt"]["max"]
            assert mix["output"]["min"] <= r["max_tokens"] <= mix["output"]["max"]


def test_open_loop_block_gaps_give_the_stated_rate():
    mix = SMALL_OPEN
    b = mix["strata"]
    reqs = traffic.plan(mix, 3, 1000, 10 * b)
    span = reqs[-1]["offset_s"]
    assert abs(span - 10 * b / mix["rate"]) < 1e-6


def test_order_seed_fixes_the_sizes_and_leaves_the_tokens_to_the_seed():
    mix = load("batch_gen")
    a = traffic.plan(mix, 1, 1000, 64)
    b = traffic.plan(mix, 2**40 + 7, 1000, 64)
    assert [(r["prompt_len"], r["max_tokens"]) for r in a] == \
        [(r["prompt_len"], r["max_tokens"]) for r in b]
    assert traffic.prompt_tokens(a[0], 1000) != traffic.prompt_tokens(
        b[0], 1000)
