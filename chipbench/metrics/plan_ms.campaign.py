"""Host time of the program's ``plan()`` per campaign execution, from
the harness's span around the call."""


def read(ctx):
    c = ctx["counters"]
    if ctx["kind"] != "campaign" or not c.get("executions"):
        return None
    return c["plan_s"] / c["executions"] * 1e3
