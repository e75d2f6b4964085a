"""Device busy time (union of the first chip's op intervals) while the
program's ``screen.call`` annotation is open, per campaign, in ms: the
device's part of the screen."""
from chipbench import attribution


def read(ctx):
    lo, hi = ctx["window"]
    spans = attribution.host_spans(ctx["events"], "screen.call", lo, hi)
    if not spans or ctx["busy_s"] <= 0:
        return None
    return 1e3 * attribution.busy_within_s(ctx["events"], spans, lo,
                                           hi) / len(spans)
