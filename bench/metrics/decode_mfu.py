"""Whole decode step's share of the chip's peak (%): ideal time of the
step's required work (the family's ``WORK["decode_step"]``: projections,
LM head, attention, embedding rows) over the device time of the traced
window's whole decode executions. Bounds any gain once a kernel is fused
away and its own roofline goes silent."""

from lib import roofline as R


def read(ctx):
    return R.share(ctx, "decode_step", lambda e: e["s"])
