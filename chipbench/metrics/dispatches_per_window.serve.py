"""Compiled-bucket dispatches the service made per window scored
(``ServiceReport.batches`` over the window)."""


def read(ctx):
    c = ctx["counters"]
    if ctx["kind"] != "serve" or not c.get("windows"):
        return None
    return c["batches"] / c["windows"]
