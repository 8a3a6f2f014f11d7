"""Training FLOPs of the scenarios completed in the window (6 x
parameters x real rows x local steps x rounds each, ``cbench.flops``)
over the window's wall time x chips x the chip's bf16 peak."""


def read(ctx):
    c, p = ctx["counters"], ctx["peaks"]
    if ctx["kind"] != "campaign" or p is None or not c.get("flops"):
        return None
    return 100.0 * c["flops"] / (ctx["window_s"] * ctx["chips"]
                                 * p["bf16_flops"])
