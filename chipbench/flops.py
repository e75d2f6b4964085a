"""Operations and least bytes of the benchmark's kernels, from the shapes
frozen in a configuration file. Nothing here reads the system under test.
"""
from __future__ import annotations


def vgg_convs(cfg: dict, h: int, w: int) -> list[tuple[int, int, int, int,
                                                          int, int]]:
    """``(C, K, R, S, H, W)`` of every conv of a VGG configuration at
    input ``h x w``: 'same' convs, each listed pool halving H and W
    (floor, as a VALID 2x2 pool does)."""
    out = []
    for i, (c, k, r, s) in enumerate(cfg["convs"]):
        out.append((c, k, r, s, h, w))
        if i in cfg["pools_after"]:
            h, w = h // 2, w // 2
    return out


def conv_flops(c: int, k: int, r: int, s: int, h: int, w: int) -> int:
    """Multiply-adds of one image's 'same' stride-1 conv, counted as 2."""
    return 2 * k * c * r * s * h * w


def conv_min_bytes(c: int, k: int, r: int, s: int, h: int, w: int,
                   batch: int, itemsize: int = 2) -> int:
    """Least HBM traffic of one conv call over ``batch`` images: the input
    and the output once each, the weights once."""
    return itemsize * (batch * (c * h * w + k * h * w) + k * c * r * s)


def vgg_flops_per_image(cfg: dict, h: int, w: int) -> int:
    return sum(conv_flops(*cv) for cv in vgg_convs(cfg, h, w))


def vgg_roofline_s(cfg: dict, h: int, w: int, batch: int, peak_flops: float,
                   peak_bytes_s: float) -> float:
    """Least time of one batch's convs: for each conv the larger of its
    operations over the peak rate and its least bytes over the peak
    bandwidth, summed over the convs."""
    return sum(max(batch * conv_flops(*cv) / peak_flops,
                   conv_min_bytes(*cv, batch) / peak_bytes_s)
               for cv in vgg_convs(cfg, h, w))
