"""The lowest over the convs of a conv's share of its roofline: its least
time for the window's batches (``conv_roofline_s``, from
``chipbench.flops``) over its ``conv2d_rows`` kernel's device time, in %.
Kernels are credited to layers by the compiled step's ``op_scopes``."""
from chipbench import attribution


def read(ctx):
    scopes, roofline = ctx.get("op_scopes"), ctx.get("conv_roofline_s")
    if not scopes or not roofline:
        return None
    times = attribution.layer_times(ctx["events"], *ctx["window"], scopes,
                                    ctx["n_devices"])
    shares = attribution.layer_rooflines(times, roofline)
    return min(shares.values()) if shares else None
