"""Shared arithmetic of the roofline readers: the whole decode executions
of the traced window, each paired with the args of the
``engine.decode.dispatch`` span that launched it (``lib/trace.py``), the
ideal time of their required work at the peaks (the work functions of the
configuration's family, ``families/``), and the device time the trace
gives it."""

import families


def decode_execs(ctx):
    """The traced window's whole decode executions that pair with a
    dispatch span."""
    tr = ctx["trace"] or {}
    return [e for e in tr.get("execs", {}).get("decode_sample", [])
            if e["dispatch"] is not None]


def ideal_s(flops, byts, peaks):
    """(seconds, bound): the least time the chip could take, and whether
    compute or memory bandwidth bounds it."""
    tc = flops / peaks["bf16_flops"]
    tm = byts / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def share(ctx, work, seconds_fn):
    """Ideal seconds of the family's ``WORK[work]`` summed over the paired
    decode executions, over ``seconds_fn(execution)`` summed over the same
    ones (%); None where the family has no such work or nothing was
    timed."""
    m, peaks = ctx["model"], ctx["peaks"]
    fn = families.of(m).WORK.get(work)
    execs = decode_execs(ctx)
    t = sum(seconds_fn(e) for e in execs)
    if fn is None or not execs or t <= 0:
        return None
    ideal = sum(ideal_s(*fn(m, e["dispatch"]), peaks)[0] for e in execs)
    return 100.0 * ideal / t


def kernel_seconds(*kernels):
    return lambda e: sum(e["kernels"].get(k, 0.0) for k in kernels)
