"""Device busy time (union of XLA op intervals, all chips) per scenario
completed in the traced window."""


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    if ctx["kind"] != "campaign" or t is None or not c.get("scenarios"):
        return None
    return t["busy_s"] * t["chips"] / c["scenarios"] * 1e3
