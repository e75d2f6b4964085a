"""The whole step's share of the chip's bf16 peak: images per second in the
traced window times the conv operations of one image (counted from the
configuration's shapes by ``chipbench.flops``), over the peak."""


def read(ctx):
    if "flops_per_image" not in ctx:
        return None
    return 100.0 * ctx["images_per_s"] * ctx["flops_per_image"] \
        / ctx["peak"]["bf16_flops"]
