"""Share of its roofline reached by decode attention (%): ideal time of
the required work (``work/paged_attention.py``) over the device time of
``paged_attention`` events inside the traced window's whole decode
executions."""

from lib import roofline as R
from work import paged_attention


def read(ctx):
    return R.share(ctx, paged_attention.work,
                   R.kernel_seconds("paged_attention"))
