"""Mean duration of the HTTP front end's pump (ms): ``server.pump`` spans
wholly inside the traced window (``lib/spans.py``), each one fan-out of
the engine's new tokens to the clients' streams after a step."""

from lib import spans


def read(ctx):
    run = spans.for_run(ctx)
    if run is None:
        return None
    v = [s.b - s.a for s in spans.inside(run, "server.pump")]
    return 1e3 * sum(v) / len(v) if v else None
