"""Share of its roofline reached by decode attention (%): ideal time of
the required work (the family's ``WORK["paged_attention"]``) over the
device time of ``paged_attention`` events inside the traced window's whole
decode executions."""

from lib import roofline as R


def read(ctx):
    return R.share(ctx, "paged_attention",
                   R.kernel_seconds("paged_attention"))
