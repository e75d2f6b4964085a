"""Crediting device time to the program's own names: the spans a traced
campaign opens as profiler annotations (``repro.obs``: ``screen.tables``,
``screen.call``, ``search.full_eval``, ...) and the layer scopes that the
VGG forward's compiled ops carry in their ``op_name`` metadata
(``jit(...)/conv4/jit(conv2d_same)/transpose``).

Like ``chipbench.trace``, it works on flattened events, so each reduction
is checked on hand-made events and on a recorded fixture without a chip.
"""
from __future__ import annotations

import re
from collections import defaultdict

from chipbench import flops, trace
from chipbench.trace import Event

#: The campaign process's own spans, kept from the host plane beside the
#: benchmark's ``bench.*`` annotations.
PROGRAM_SPANS = frozenset({"campaign", "screen.jax", "screen.tables",
                           "screen.call", "cell.run", "cell.eval",
                           "search.full_eval", "store.append"})

KERNEL = "conv2d_rows"

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.-]+) = .*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"')
_LAYER = re.compile(r"^(conv|pool)\d+$")


def keep(events: list[Event]) -> list[Event]:
    """``trace.keep``'s events, and the program's spans on the host."""
    return [e for e in events if trace.is_device_op(e)
            or (e.plane.startswith(trace.HOST_PLANE)
                and (e.name.startswith(trace.BENCH_PREFIX)
                     or e.name in PROGRAM_SPANS))]


def host_spans(events: list[Event], name: str, lo: float,
               hi: float) -> list[Event]:
    """The host annotations ``name`` that lie inside ``[lo, hi]``."""
    return [e for e in events if e.name == name
            and e.plane.startswith(trace.HOST_PLANE)
            and lo <= e.start_ns and e.end_ns <= hi]


def busy_within_s(events: list[Event], spans: list[Event], lo: float,
                  hi: float) -> float:
    """Seconds in which some op ran on the first chip while one of
    ``spans`` was open, inside the window."""
    per = trace.device_ops(events, lo, hi)
    if not per or not spans:
        return 0.0
    busy = trace.merge((max(o.start_ns, lo), min(o.end_ns, hi))
                       for o in per[sorted(per)[0]])
    open_ = trace.merge((s.start_ns, s.end_ns) for s in spans)
    total, i, j = 0.0, 0, 0
    while i < len(busy) and j < len(open_):
        s = max(busy[i][0], open_[j][0])
        e = min(busy[i][1], open_[j][1])
        if e > s:
            total += e - s
        if busy[i][1] < open_[j][1]:
            i += 1
        else:
            j += 1
    return total / 1e9


def op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: op_name}`` of the entry computation of a
    compiled program's HLO text: its top-level instructions, which are
    the ops a device trace names."""
    out, entry = {}, False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            entry = True
        elif entry and line.startswith("}"):
            break
        elif entry and (m := _INSTR.match(line)):
            out[m.group(1)] = m.group(2)
    return out


def layer_of(op_name: str) -> str | None:
    """The layer scope (``conv4``, ``pool6``) an ``op_name`` lies under."""
    for part in op_name.split("/"):
        if _LAYER.match(part):
            return part
    return None


def layer_times(events: list[Event], lo: float, hi: float,
                scopes: dict[str, str], n_devices: int) -> dict:
    """Device seconds in the window, averaged over the chips used, of
    ``{"kernel": {layer: s}, "other": {layer: s}, "unscoped": s,
    "total": s}``: each op under a layer scope is the layer's kernel
    (``conv2d_rows``) or its other work (the wrapper's pad and
    transposes, the ReLU fused with them, a pool); an op whose
    instruction carries no layer scope is unscoped."""
    kernel: dict[str, float] = defaultdict(float)
    other: dict[str, float] = defaultdict(float)
    unscoped = total = 0.0
    for ops in trace.device_ops(events, lo, hi).values():
        for o in ops:
            t = (min(o.end_ns, hi) - max(o.start_ns, lo)) / 1e9 / n_devices
            total += t
            layer = layer_of(scopes.get(o.name, ""))
            if layer is None:
                unscoped += t
            elif o.name.startswith(KERNEL):
                kernel[layer] += t
            else:
                other[layer] += t
    return {"kernel": dict(kernel), "other": dict(other),
            "unscoped": unscoped, "total": total}


def conv_layers(names) -> list[str]:
    """The conv layer scopes among ``names``, in network order (the
    layers are numbered in order)."""
    return sorted((n for n in set(names) if n.startswith("conv")),
                  key=lambda n: int(n[4:]))


def conv_roofline_s(cfg: dict, h: int, w: int, batch: int,
                    peak_flops: float, peak_bytes_s: float) -> list[float]:
    """Least time of one batch through each conv of a VGG configuration,
    in network order: the larger of its operations over the peak rate and
    its least bytes over the peak bandwidth."""
    return [max(batch * flops.conv_flops(*cv) / peak_flops,
                flops.conv_min_bytes(*cv, batch) / peak_bytes_s)
            for cv in flops.vgg_convs(cfg, h, w)]


def layer_rooflines(times: dict, roofline_s: list[float]) -> dict | None:
    """``{conv layer: roofline share (%)}``: each conv's roofline time for
    the window (``roofline_s``, in network order) over its kernel's
    device time; None where the scoped convs are not one per entry."""
    convs = conv_layers(times["kernel"])
    if not convs or len(convs) != len(roofline_s):
        return None
    return {n: 100.0 * r / times["kernel"][n]
            for n, r in zip(convs, roofline_s)}


def layer_table(times: dict, shares: dict | None) -> list[str]:
    """Lines of a per-layer table: each layer's kernel and other device
    time, the conv's roofline share, and the share of the device time
    that maps to no layer."""
    layers = sorted(set(times["kernel"]) | set(times["other"]),
                    key=lambda n: int(re.sub(r"\D", "", n)))
    rows = [f"{'layer':<8} {'kernel_ms':>10} {'other_ms':>10} "
            f"{'roofline_%':>10}"]
    for n in layers:
        share = (shares or {}).get(n)
        rows.append(f"{n:<8} {1e3 * times['kernel'].get(n, 0.0):>10.3f} "
                    f"{1e3 * times['other'].get(n, 0.0):>10.3f} "
                    f"{'' if share is None else f'{share:.2f}':>10}")
    share = 100.0 * times["unscoped"] / times["total"] \
        if times["total"] > 0 else 0.0
    rows.append(f"unscoped: {1e3 * times['unscoped']:.3f} ms, {share:.3f}% "
                f"of {1e3 * times['total']:.3f} ms device time")
    return rows
