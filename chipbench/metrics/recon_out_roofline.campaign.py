"""Share of its roofline that the autoencoder's fused output-layer
kernel (device ops ``recon_out.<n>``) reaches over the traced window:
the least time its calls could take, the larger of their FLOPs at the
bf16 peak times the MXU passes of the configuration's matmul precision
and their HBM bytes at the peak bandwidth (both counted from the
buckets' shapes by the body's ``kernel_counts``), over the device time
of those ops.  Nothing where the kernel did not run."""
from cbench.peaks import MATMUL_PASSES

PREFIX = "recon_out"


def read(ctx):
    t, p = ctx["trace"], ctx["peaks"]
    if ctx["kind"] != "campaign" or t is None or p is None:
        return None
    ran = t.get("kernels", {}).get(PREFIX)
    if not ran or not ran["calls"]:
        return None
    work = ctx["counters"]["kernels"][PREFIX]
    if work["calls"] != ran["calls"]:
        raise RuntimeError(
            f"{PREFIX}: {ran['calls']} calls in the trace, {work['calls']} "
            f"counted from the buckets' shapes")
    least = max(MATMUL_PASSES[ctx["precision"]] * work["flops"]
                / p["bf16_flops"], work["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / ran["seconds"]
