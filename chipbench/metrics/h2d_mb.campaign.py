"""Megabytes (1e6 bytes) of host arrays the program's array builds put
on the device per campaign execution (the ``campaign.h2d_bytes``
counter), from the program's execution registry."""
from cbench.programstats import window_mean


def read(ctx):
    v = window_mean(ctx, "h2d_bytes")
    return None if v is None else v / 1e6
