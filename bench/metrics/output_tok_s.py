"""Output tokens received by the clients inside the window, over the
window's length. Host clock, client side."""

from lib import stats


def read(ctx):
    n = stats.tokens_in_window(ctx["client"]["requests"], ctx["t0"],
                               ctx["t1"])
    return n / (ctx["t1"] - ctx["t0"]) if n else None
