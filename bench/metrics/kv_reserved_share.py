"""Share of the KV page pool reserved at admission but not yet written
(%): mean, over the traced window's engine steps that dispatched a
decode, of ``kv_pages_reserved`` / (``kv_pages_in_use`` +
``kv_pages_reserved`` + ``kv_pages_free``), the gauges the ``engine.step``
span carries (``lib/spans.py``). Pages held back for tokens not yet
generated cap the live batch (``batch_occupancy``)."""

from lib import spans


def read(ctx):
    run = spans.for_run(ctx)
    if run is None:
        return None
    shares = []
    for s in spans.steps(run):
        if spans.DISPATCH not in s["kids"]:
            continue
        g = [s["span"].args[k] for k in spans.GAUGES]
        shares.append(g[1] / sum(g))
    return 100.0 * sum(shares) / len(shares) if shares else None
