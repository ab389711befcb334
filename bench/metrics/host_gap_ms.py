"""Host time between consecutive decode executions (ms): mean, over
consecutive engine steps k, k+1 of the traced window that both decode, of
the end of ``engine.decode.dispatch`` in k+1 less the end of
``engine.decode.wait`` in k. Program spans (``lib/spans.py``): what the
engine's host loop, the server pump and any prefill between them add to
every token gap."""

from lib import spans


def read(ctx):
    run = spans.for_run(ctx)
    if run is None:
        return None
    dec = {s["step"]: s["kids"] for s in spans.steps(run)
           if spans.DISPATCH in s["kids"] and spans.WAIT in s["kids"]}
    gaps = [dec[k + 1][spans.DISPATCH][-1].b - dec[k][spans.WAIT][-1].b
            for k in dec if k + 1 in dec]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
