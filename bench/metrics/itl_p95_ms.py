"""95th percentile of every gap between consecutive token events of every
request, pooled over the window (ms). Host clock, client side."""

from lib import stats


def read(ctx):
    v = stats.itl_values(ctx["client"]["requests"], ctx["t0"], ctx["t1"])
    return stats.percentile(v, 95) * 1e3 if v else None
