"""Device time of the ops under a conv layer's scope other than its
``conv2d_rows`` kernel (the wrapper's pad and transposes, and the ReLU
fused with them) over the device's busy time, in %. Ops are credited to
layers by the compiled step's ``op_scopes``."""
from chipbench import attribution


def read(ctx):
    scopes = ctx.get("op_scopes")
    if not scopes or ctx["busy_s"] <= 0:
        return None
    times = attribution.layer_times(ctx["events"], *ctx["window"], scopes,
                                    ctx["n_devices"])
    convs = attribution.conv_layers(times["kernel"])
    if not convs:
        return None
    return 100.0 * sum(times["other"].get(n, 0.0) for n in convs) \
        / ctx["busy_s"]
