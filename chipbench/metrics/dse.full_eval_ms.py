"""The program's ``search.full_eval`` annotations summed over the traced
window, per ``cell.eval`` annotation (one a cell), in ms: the batched
full-fidelity evaluation inside a cell's search."""
from chipbench import attribution


def read(ctx):
    lo, hi = ctx["window"]
    evals = attribution.host_spans(ctx["events"], "search.full_eval", lo, hi)
    cells = attribution.host_spans(ctx["events"], "cell.eval", lo, hi)
    if not evals or not cells:
        return None
    return 1e3 * sum(e.dur_ns for e in evals) / 1e9 / len(cells)
