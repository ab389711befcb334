"""Shared arithmetic of the roofline readers: the whole decode executions
of the traced window, each paired with the live contexts of the call that
launched it, the ideal time of their required work (``bench/work``) at the
peaks, and the device time the trace gives it."""

from work import decode_step


def decode_execs(ctx):
    """[(execution, contexts)] for the traced window's whole decode
    executions whose launching call the harness logged."""
    tr = ctx["trace"] or {}
    log = ctx["decode_log"]
    return [(e, log[e["call"]])
            for e in tr.get("execs", {}).get("decode_sample", [])
            if e["call"] in log]


def share(ctx, work_fn, seconds_fn):
    """Ideal seconds of ``work_fn(model, contexts)`` summed over the decode
    executions, over ``seconds_fn(execution)`` summed over the same ones
    (%); None where nothing was timed."""
    pairs = decode_execs(ctx)
    t = sum(seconds_fn(e) for e, _ in pairs)
    if not pairs or t <= 0:
        return None
    m, peaks = ctx["model"], ctx["peaks"]
    ideal = sum(decode_step.ideal_s(*work_fn(m, list(c)), peaks)[0]
                for _, c in pairs)
    return 100.0 * ideal / t


def kernel_seconds(*kernels):
    return lambda e: sum(e["kernels"].get(k, 0.0) for k in kernels)
