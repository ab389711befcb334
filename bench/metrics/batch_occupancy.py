"""Mean live slots per decode step over the window, as a share of the
engine's slots (%). Engine counter ``stats["slot_occupancy"]``."""


def read(ctx):
    occ = ctx["occupancy"]
    return 100.0 * sum(occ) / len(occ) / ctx["n_slots"] if occ else None
