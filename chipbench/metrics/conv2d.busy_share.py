"""Device time inside the ``conv2d_rows`` kernel over the device's busy
time; the rest is the wrapper's pad and transposes, the pools and the
ReLUs."""
from chipbench import trace

KERNEL = "conv2d_rows"


def read(ctx):
    lo, hi = ctx["window"]
    t = trace.kernel_s(ctx["events"], lo, hi, KERNEL, ctx["n_devices"])
    if t <= 0 or ctx["busy_s"] <= 0:
        return None
    return 100.0 * t / ctx["busy_s"]
