"""The conv kernel's share of its roofline: the least time of the window's
convs (for each conv the larger of operations over peak FLOP/s and least
bytes over peak bandwidth, ``chipbench.flops``) over the device time of
the ``conv2d_rows`` kernel in the trace."""
from chipbench import trace

KERNEL = "conv2d_rows"


def read(ctx):
    if "roofline_s" not in ctx:
        return None
    lo, hi = ctx["window"]
    t = trace.kernel_s(ctx["events"], lo, hi, KERNEL, ctx["n_devices"])
    if t <= 0:
        return None
    return 100.0 * ctx["roofline_s"] / t
