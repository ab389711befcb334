"""Whole decode step's share of the chip's peak (%): ideal time of the
step's required work (``work/decode_step.py``: projections, LM head,
attention, embedding rows) over the device time of the traced window's
whole decode executions. Bounds any gain once a kernel is fused away and
its own roofline goes silent."""

from lib import roofline as R
from work import decode_step


def read(ctx):
    return R.share(ctx, decode_step.work, lambda e: e["s"])
