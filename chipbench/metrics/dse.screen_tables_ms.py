"""Mean per campaign of the program's ``screen.tables`` annotations in the
traced window, in ms: the host's part of the device screen (per-cell
set-up, rung-0 blocks, the cells' tables, stacking them)."""
from chipbench import attribution


def read(ctx):
    spans = attribution.host_spans(ctx["events"], "screen.tables",
                                   *ctx["window"])
    if not spans:
        return None
    return 1e3 * sum(s.dur_ns for s in spans) / 1e9 / len(spans)
