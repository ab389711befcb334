"""Preemptions in the window (engine counter) per 100 requests the engine
finished in the window."""


def read(ctx):
    done = sum(1 for e in ctx["engine_records"].values()
               if e["status"] == "FINISHED"
               and ctx["t0"] <= e["end"] < ctx["t1"])
    return 100.0 * ctx["window"]["preemptions"] / done if done else None
