"""Plain reference of the FPGA analytical model that the DSE campaigns
price designs with (DNNExplorer, arXiv:2008.12745, Sec. 6-7): the rung-0
screening relaxation, and the full evaluation of one resource allocation
vector (RAV) by Algorithm 2 (pipeline structure) and Algorithm 3 (generic
structure).

It reads only the configuration file's frozen layer tables and part
specs, and imports nothing of the system under test. ``ftype`` is the
float type every real-valued quantity is carried in: ``float`` (64 bits) as
the configuration states, ``np.float32`` for the control that has to
come out not correct.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

BRAM_BITS = 18 * 1024
# buffer-capacity fractions per generic-structure strategy (Sec. 5.3.2)
ABUFF_FRAC = {1: 0.25, 2: 0.15}
FMBUFF_FRAC = {1: 0.75, 2: 0.35}
WBUFF_FRAC = {1: 0.0, 2: 0.50}
OBJECTIVES = ("throughput_ips", "gops", "latency_s", "dsp_eff", "bram_used",
              "feasible")


@dataclasses.dataclass(frozen=True)
class Layer:
    kind: str           # conv | dwconv | fc | pool
    h: int              # output rows
    w: int
    c: int
    k: int
    r: int = 1
    s: int = 1
    stride: int = 1
    groups: int = 1

    @property
    def macs(self) -> int:
        if self.kind == "pool":
            return 0
        return self.h * self.w * self.r * self.s * (self.c // self.groups) \
            * self.k

    def weight_bytes(self, bits: int) -> int:
        if self.kind == "pool":
            return 0
        return self.r * self.s * (self.c // self.groups) * self.k * bits // 8

    def ifm_bytes(self, bits: int) -> int:
        return self.h * self.stride * self.w * self.stride * self.c * bits // 8

    def ofm_bytes(self, bits: int) -> int:
        return self.h * self.w * self.k * bits // 8


@dataclasses.dataclass(frozen=True)
class Part:
    dsp: int
    bram18k: int
    bw_gbps: float
    freq_mhz: float
    usable_frac: float

    @property
    def dsp_usable(self) -> int:
        return int(self.dsp * self.usable_frac)

    @property
    def bram_usable(self) -> int:
        return int(self.bram18k * self.usable_frac)


def vgg_layers(plan, h: int, w: int) -> list[Layer]:
    """A VGG net at input h x w: 3x3 'same' convs, a 2x2 max pool after
    each group (floor division, as a VALID pool gives)."""
    out, c = [], 3
    for group in plan:
        for k in group:
            out.append(Layer("conv", h, w, c, k, 3, 3))
            c = k
        h, w = h // 2, w // 2
        out.append(Layer("pool", h, w, c, c, 2, 2, 2))
    return out


def net_layers(cfg: dict, net: str, h: int = 0, w: int = 0) -> list[Layer]:
    """The layer table of one campaign cell's net, from the configuration:
    a VGG plan at the cell's input, or a frozen table at its own input."""
    if net in cfg["vgg_plans"]:
        return vgg_layers(cfg["vgg_plans"][net], h or 224, w or 224)
    entry = cfg["nets"][net]
    if h and [h, w] != entry["input"]:
        raise ValueError(f"{net} is frozen at {entry['input']}, not {h}x{w}")
    return [Layer(*row) for row in entry["layers"]]


def alpha_for(bits: int) -> int:
    """MAC-ops per DSP per cycle (Eq. 1): 4 at 8 bits or fewer, else 2."""
    return 4 if bits <= 8 else 2


def _pow2_floor(x) -> int:
    return 1 << max(0, int(math.floor(math.log2(max(x, 1)))))


def split_pf(pf: int, c: int, k: int) -> tuple[int, int]:
    """Near-square power-of-two (CPF, KPF) with CPF <= C, KPF <= K."""
    pf = max(1, _pow2_floor(pf))
    cpf = min(_pow2_floor(math.sqrt(pf)), _pow2_floor(c))
    kpf = min(pf // cpf, _pow2_floor(k))
    cpf = min(pf // kpf, _pow2_floor(c))
    return max(1, cpf), max(1, kpf)


# ---------------------------------------------------------------------------
# Pipeline structure (Sec. 6.1, Algorithm 2)
# ---------------------------------------------------------------------------


class Pipeline:
    """One stage per layer with its (CPF, KPF); frames stream through."""

    def __init__(self, layers, pfs, dw, ww, batch, ftype):
        self.layers, self.dw, self.ww = layers, dw, ww
        self.batch, self.f = batch, ftype
        self.cpf_kpf = [split_pf(pf, l.c, l.k) for l, pf in zip(layers, pfs)]

    def pfs(self) -> list[int]:
        return [c * k for c, k in self.cpf_kpf]

    def comp(self, i: int, freq):
        c, k = self.cpf_kpf[i]
        return self.layers[i].macs / (c * k * freq)

    def dsp(self) -> int:
        alpha = alpha_for(min(self.dw, self.ww))
        return sum(max(1, 2 * c * k // alpha) for c, k in self.cpf_kpf)

    def bram(self) -> int:
        total = 0
        for l, (c, k) in zip(self.layers, self.cpf_kpf):
            col = math.ceil(l.c * l.h * l.stride * (l.s + 1) * self.dw
                            / BRAM_BITS)
            banks = max(1, math.ceil(c * self.dw / 36))
            wbits = 2 * l.r * l.s * c * k * self.ww
            total += max(banks, col) + max(1, math.ceil(wbits / BRAM_BITS))
        return total

    def latency(self, freq, bw):
        if not self.layers:
            return self.f(0.0)
        comp = self.batch * max(self.comp(i, freq)
                                for i in range(len(self.layers)))
        stream = (sum(l.weight_bytes(self.ww) for l in self.layers)
                  + self.batch * self.layers[0].ifm_bytes(self.dw))
        mem = stream / bw if bw > 0 else self.f(math.inf)
        return max(comp, mem)

    def halved(self) -> "Pipeline":
        return Pipeline(self.layers, [max(1, pf // 2) for pf in self.pfs()],
                        self.dw, self.ww, self.batch, self.f)


def design_pipeline(layers, dsp_cap, bram_cap, bw, freq, dw, ww, batch,
                    ftype) -> Pipeline:
    """Algorithm 2: CTC-proportional PFs, halve until the design fits,
    then double the slowest stage while it still fits."""
    wtotal = sum(l.weight_bytes(ww) for l in layers)
    if wtotal == 0 or bw <= 0:
        pfs = [1] * len(layers)
    else:
        pfs = [max(1, _pow2_floor(l.macs * bw / wtotal / freq))
               for l in layers]
    p = Pipeline(layers, pfs, dw, ww, batch, ftype)
    while layers and (p.dsp() > dsp_cap or p.bram() > bram_cap):
        if all(pf == 1 for pf in p.pfs()):
            break
        p = p.halved()
    while layers:
        i = max(range(len(layers)), key=lambda j: p.comp(j, freq))
        l, pf = layers[i], p.pfs()[i]
        if pf >= l.c * l.k:
            break
        bumped = split_pf(pf * 2, l.c, l.k)
        if bumped[0] * bumped[1] <= pf:
            break
        trial = Pipeline(layers, p.pfs(), dw, ww, batch, ftype)
        trial.cpf_kpf = p.cpf_kpf[:i] + [bumped] + p.cpf_kpf[i + 1:]
        if trial.dsp() > dsp_cap or trial.bram() > bram_cap:
            break
        p = trial
    return p


# ---------------------------------------------------------------------------
# Generic structure (Sec. 6.2, Algorithm 3)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Generic:
    cpf: int
    kpf: int
    dw: int
    ww: int
    bram: int
    bw: object
    strategy: int
    ftype: type

    def dsp(self) -> int:
        return max(1, 2 * self.cpf * self.kpf // alpha_for(min(self.dw,
                                                               self.ww)))

    def _cap(self, frac: dict) -> int:
        return int(self.bram * BRAM_BITS * frac[self.strategy])

    def _fits(self, l: Layer, batch: int) -> bool:
        need = batch * (l.ifm_bytes(self.dw) + l.ofm_bytes(self.dw)) * 8
        return need <= self._cap(FMBUFF_FRAC) // 2

    def layer_latency(self, l: Layer, freq, batch: int):
        f = self.ftype
        if l.kind == "pool":
            if self._fits(l, batch):
                return f(0.0)
            if self.bw <= 0:
                return f(math.inf)
            return batch * (l.ifm_bytes(self.dw) + l.ofm_bytes(self.dw)) \
                / self.bw
        if l.kind == "dwconv":
            cycles = l.h * l.w * l.r * l.s * math.ceil(l.c / self.cpf)
        else:
            cycles = (l.h * l.w * l.r * l.s
                      * math.ceil(l.c // l.groups / self.cpf)
                      * math.ceil(l.k / self.kpf))
        comp = batch * (cycles / freq)
        wb = l.weight_bytes(self.ww)
        g_fm = max(1, math.ceil(batch * l.h * l.w * l.k * self.dw
                                / max(1, self._cap(ABUFF_FRAC) // 2)))
        traffic = wb * g_fm
        if not self._fits(l, batch):
            traffic += batch * (l.ifm_bytes(self.dw) + l.ofm_bytes(self.dw))
        if self.strategy == 2:
            need_w = l.r * l.s * (l.c // l.groups) * l.k * self.ww
            g_w = max(1, math.ceil(need_w / max(1, self._cap(WBUFF_FRAC)
                                                // 2)))
            ws = wb + batch * (l.ifm_bytes(self.dw) * g_w
                               + l.ofm_bytes(self.dw))
            traffic = min(traffic, ws)
        mem = traffic / self.bw if self.bw > 0 else f(math.inf)
        return max(comp, mem)

    def latency(self, layers, freq, batch: int):
        # sum() of Python floats is compensated (Neumaier) since 3.12
        return sum(self.layer_latency(l, freq, batch) for l in layers)


def best_generic(layers, cpf, kpf, dw, ww, bram, bw, freq, batch, ftype):
    cands = [Generic(cpf, kpf, dw, ww, bram, bw, s, ftype) for s in (1, 2)]
    return min(cands, key=lambda g: g.latency(layers, freq, batch))


def evaluate(layers: list[Layer], part: Part, rav: dict, dw: int, ww: int,
             ftype=float, max_rollbacks: int = 12) -> dict:
    """Objectives of one RAV ``{sp, batch, dsp_frac, bram_frac,
    bw_frac}``: Algorithm 2 for the first ``sp`` major layers, Algorithm 3
    for the rest, rolling the pipeline back while the generic structure
    does not fit."""
    f = ftype
    freq = f(part.freq_mhz * 1e6)
    bw_total = f(part.bw_gbps * 1e9)
    majors = [l for l in layers if l.kind != "pool"]
    sp = max(0, min(int(rav["sp"]), len(majors)))
    batch = int(rav["batch"])
    pipe_layers = majors[:sp]
    # everything after the sp-th major layer; a pool right after a
    # pipelined layer stays with its stage
    gen_layers, seen = [], 0
    for l in layers:
        seen += l.kind != "pool"
        if seen > sp:
            gen_layers.append(l)
    dsp_p = int(part.dsp_usable * rav["dsp_frac"]) if sp else 0
    bram_p = int(part.bram_usable * rav["bram_frac"]) if sp else 0
    bw_p = bw_total * f(rav["bw_frac"]) if sp else f(0.0)
    bw_g = bw_total - bw_p
    alpha = alpha_for(min(dw, ww))

    pipe = design_pipeline(pipe_layers, dsp_p, bram_p, bw_p, freq, dw, ww,
                           batch, f)
    gen = None
    if gen_layers:
        for _ in range(max_rollbacks):
            dsp_avail = part.dsp_usable - pipe.dsp()
            bram_avail = part.bram_usable - pipe.bram()
            if dsp_avail < 1 or bram_avail < 1:
                if not pipe.layers or all(pf == 1 for pf in pipe.pfs()):
                    break
                pipe = pipe.halved()
                continue
            target = pipe.latency(freq, bw_p) if pipe.layers else None
            pf_cap = max(1, dsp_avail * alpha // 2)
            c_max = max(l.c for l in gen_layers)
            k_max = max(l.k for l in gen_layers)
            pf, gen = 1, None
            while True:
                cpf, kpf = split_pf(pf, c_max, k_max)
                cand = best_generic(gen_layers, cpf, kpf, dw, ww, bram_avail,
                                    bw_g, freq, batch, f)
                if cand.dsp() > dsp_avail:
                    break
                gen = cand
                lat = gen.latency(gen_layers, freq, batch)
                if target is not None and lat <= target:
                    break
                if pf >= pf_cap or cpf * kpf < pf:
                    break
                pf *= 2
            if gen is None:
                if not pipe.layers or all(pf == 1 for pf in pipe.pfs()):
                    break
                pipe = pipe.halved()
                continue
            break

    if not pipe.layers and gen is None:
        return dict(throughput_ips=0.0, gops=0.0, latency_s=0.0, dsp_eff=0.0,
                    bram_used=0.0, feasible=False)
    if pipe.layers:
        lat_p = pipe.latency(freq, bw_p)
        rate_p = batch / lat_p if lat_p > 0 else f(0.0)
    else:
        lat_p, rate_p = f(0.0), f(math.inf)
    if gen is not None:
        lat_g = gen.latency(gen_layers, freq, batch)
        rate_g = batch / lat_g if lat_g > 0 else f(math.inf)
    else:
        lat_g, rate_g = f(0.0), f(math.inf)
    rate = min(rate_p, rate_g)
    if not math.isfinite(rate):
        rate = f(0.0)
    dsp_used = pipe.dsp() + (gen.dsp() if gen else 0)
    bram_used = pipe.bram() + (gen.bram if gen else 0)
    total_ops = 2 * sum(l.macs for l in layers)
    gops = rate * total_ops / f(1e9)
    dsp_eff = gops * f(1e9) / (alpha * dsp_used * freq) if dsp_used \
        else f(0.0)
    return dict(throughput_ips=float(rate), gops=float(gops),
                latency_s=float(lat_p + lat_g), dsp_eff=float(dsp_eff),
                bram_used=float(bram_used),
                feasible=bool(dsp_used <= part.dsp_usable
                              and bram_used <= part.bram_usable))


# ---------------------------------------------------------------------------
# Rung-0 screen: the relaxed roofline the hyperband searcher triages with
# ---------------------------------------------------------------------------


def screen(layers: list[Layer], part: Part, positions, dw: int, ww: int,
           xp=np, ftype=np.float64, itype=np.int64):
    """Relaxed throughput (img/s) of every row ``[sp, batch, dsp_frac,
    bram_frac, bw_frac]`` of ``positions``: the split's MACs at the
    continuous DSP roofline against its weight and input stream, the
    rest on the remaining DSPs against its weights read once; BRAM and
    Algorithm 2/3's loops are left out. ``xp`` is NumPy, or jax.numpy
    for the control on the device."""
    majors = [l for l in layers if l.kind != "pool"]
    n_major, n_layers = len(majors), len(layers)
    major_at = [i for i, l in enumerate(layers) if l.kind != "pool"]
    seg_start = np.array(major_at + [n_layers])   # first generic layer, per sp
    pipe_macs = np.concatenate([[0.0], np.cumsum(
        [float(l.macs) for l in majors])])
    pipe_w = np.concatenate([[0.0], np.cumsum(
        [float(l.weight_bytes(ww)) for l in majors])])
    tail_macs = np.concatenate([np.cumsum(
        [float(l.macs) for l in layers][::-1])[::-1], [0.0]])
    tail_w = np.concatenate([np.cumsum(
        [float(l.weight_bytes(ww)) for l in layers][::-1])[::-1], [0.0]])
    ifm0 = float(majors[0].ifm_bytes(dw)) if majors else 0.0
    alpha = alpha_for(min(dw, ww))
    freq = ftype(part.freq_mhz * 1e6)
    bw_total = ftype(part.bw_gbps * 1e9)
    tab = {k: xp.asarray(v, dtype=ftype) for k, v in
           dict(pipe_macs=pipe_macs, pipe_w=pipe_w, tail_macs=tail_macs,
                tail_w=tail_w).items()}
    seg_start = xp.asarray(seg_start, dtype=itype)

    arr = xp.asarray(positions, dtype=ftype)
    sp = xp.clip(xp.round(arr[:, 0]).astype(itype), 0, n_major)
    batch = xp.maximum(ftype(1.0), xp.round(arr[:, 1]))
    has_pipe = sp > 0
    dsp_p = xp.where(has_pipe, (part.dsp_usable * arr[:, 2]).astype(itype), 0)
    bw_p = xp.where(has_pipe, bw_total * arr[:, 4], ftype(0.0))
    pf_p = xp.maximum(1, dsp_p * alpha // 2).astype(ftype)
    comp_p = batch * tab["pipe_macs"][sp] / (pf_p * freq)
    stream = tab["pipe_w"][sp] + batch * ftype(ifm0)
    inf = ftype(np.inf)
    with np.errstate(divide="ignore", invalid="ignore"):
        mem_p = xp.where(bw_p > 0, stream / bw_p,
                         xp.where(stream > 0, inf, ftype(0.0)))
        lat_p = xp.where(has_pipe, xp.maximum(comp_p, mem_p), ftype(0.0))
        start = seg_start[sp]
        tm, tw = tab["tail_macs"][start], tab["tail_w"][start]
        pf_g = xp.maximum(1, xp.maximum(0, part.dsp_usable - dsp_p)
                          * alpha // 2).astype(ftype)
        comp_g = batch * tm / (pf_g * freq)
        bw_g = bw_total - bw_p
        mem_g = xp.where(bw_g > 0, tw / bw_g,
                         xp.where(tw > 0, inf, ftype(0.0)))
        lat_g = xp.where(start < n_layers, xp.maximum(comp_g, mem_g),
                         ftype(0.0))
        lat = xp.maximum(lat_p, lat_g)
        return xp.where((lat > 0) & xp.isfinite(lat), batch / lat,
                        ftype(0.0))
