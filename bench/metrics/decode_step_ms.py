"""Mean device time of one decode-program execution (ms), over the
executions that lie wholly inside the traced window."""


def read(ctx):
    v = ((ctx["trace"] or {}).get("programs", {})).get("decode_sample", [])
    return 1e3 * sum(v) / len(v) if v else None
