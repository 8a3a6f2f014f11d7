"""Share of the traced window in which no XLA operation ran on the
device (averaged over chips)."""


def read(ctx):
    t = ctx["trace"]
    if ctx["kind"] != "serve" or t is None:
        return None
    return 100.0 * t["idle_share"]
