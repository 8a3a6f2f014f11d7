"""Generation-1 and -2 garbage-collection pause inside each campaign
execution, per execution, from the program's execution registry (a
pause stops every thread, so it counts against every execution in
flight)."""
from cbench.programstats import window_mean


def read(ctx):
    v = window_mean(ctx, "gc_s")
    return None if v is None else v * 1e3
