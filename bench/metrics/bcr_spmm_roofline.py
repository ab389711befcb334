"""Share of its roofline reached by the BCR projections in decode (%):
ideal time of the required work (``work/bcr_spmm.py``) over the device
time of ``bcr_spmm`` and ``bcr_spmm_grouped`` events inside the traced
window's whole decode executions."""

from lib import roofline as R
from work import bcr_spmm


def read(ctx):
    return R.share(ctx, lambda m, c: bcr_spmm.work(m, len(c)),
                   R.kernel_seconds("bcr_spmm", "bcr_spmm_grouped"))
