"""Host time of the program's ``campaign.post`` spans per campaign
execution (AUROC post-processing and result slicing), from the
program's execution registry."""
from cbench.programstats import window_mean


def read(ctx):
    v = window_mean(ctx, "post_s")
    return None if v is None else v * 1e3
