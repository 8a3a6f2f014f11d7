"""Wall time of the program's ``campaign.build`` spans per campaign
execution (host arrays, trace stacking, padding and the host-to-device
transfers), from the program's execution registry.  The stacking,
gathers and dtype conversions run as eager device operations, so with
several executions in flight on one chip this mostly times their wait
behind the other executions' programs."""
from cbench.programstats import window_mean


def read(ctx):
    v = window_mean(ctx, "build_s")
    return None if v is None else v * 1e3
