"""Share of its roofline reached by the BCR projections in decode (%):
ideal time of the required work (the family's ``WORK["bcr_spmm"]``) over
the device time of ``bcr_spmm`` and ``bcr_spmm_grouped`` events inside the
traced window's whole decode executions."""

from lib import roofline as R


def read(ctx):
    return R.share(ctx, "bcr_spmm",
                   R.kernel_seconds("bcr_spmm", "bcr_spmm_grouped"))
