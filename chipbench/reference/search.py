"""Plain reference of the campaign's search engine (DNNExplorer,
arXiv:2008.12745, Sec. 7, with the hyperband triage in front of it): for
one campaign cell, the RAV its search returns, that RAV's fitness, and
how much searching it took: candidates screened, distinct full
evaluations, and the swarm's iterations.

The search as the configuration states it:

1. Rung 0 draws ``screen`` uniform positions ``[sp, batch, dsp_frac,
   bram_frac, bw_frac]`` from a generator seeded with the cell's seed,
   plants the three canonical ones on top, and ranks them by the
   screening relaxation (:func:`chipbench.reference.fpga.screen`).
2. The canonical three and the top ``survivors`` screened positions,
   deduplicated at the memo resolution, are promoted to the full
   evaluation (:func:`chipbench.reference.fpga.evaluate`); the fitness is
   the feasible throughput.
3. A particle swarm (Algorithm 1) seeded with the ranked survivors, its
   generator seeded with the cell's seed plus one, refines the best;
   it stops after ``patience`` iterations without a new best or after
   ``iterations``.

Full evaluations are memoized on the RAV rounded to the memo resolution:
a position whose rounded RAV was seen before takes that RAV's fitness.
``ftype`` carries positions, velocities and every model quantity:
``np.float64`` as the configuration states, ``np.float32`` for the
control. It imports nothing of the system under test.
"""
from __future__ import annotations

import hashlib

import numpy as np

from chipbench.reference import fpga


def cell_key(cell: dict) -> str:
    size = f"{cell['h']}x{cell['w']}" if cell["h"] else "native"
    return (f"net={cell['net']}|in={size}|fpga={cell['fpga']}"
            f"|prec={cell['precision']}|bmax={cell['batch_max']}")


def cell_seed(base_seed: int, cell: dict) -> int:
    """The cell's search seed: the first four bytes of the SHA-256 of the
    campaign's base seed and the cell's key, as a 31-bit integer."""
    digest = hashlib.sha256(f"{base_seed}|{cell_key(cell)}".encode()).digest()
    return int.from_bytes(digest[:4], "big") & 0x7FFFFFFF


def to_rav(p) -> dict:
    return dict(sp=int(round(p[0])), batch=max(1, int(round(p[1]))),
                dsp_frac=float(p[2]), bram_frac=float(p[3]),
                bw_frac=float(p[4]))


class Search:
    """One cell's search; :meth:`run` gives its outcome."""

    def __init__(self, cfg: dict, layers, part: fpga.Part, cell: dict,
                 base_seed: int, ftype=np.float64):
        self.s = cfg["search"]
        self.population, self.iterations = cfg["population"], cfg["iterations"]
        self.screen = cfg["searcher_config"]["screen"]
        self.layers, self.part, self.f = layers, part, ftype
        self.bits = cell["precision"]
        self.seed = cell_seed(base_seed, cell)
        lo_frac, hi_frac = self.s["frac_bounds"]
        sp_max = sum(l.kind != "pool" for l in layers)
        self.lo = np.array([0.0, 1.0, lo_frac, lo_frac, lo_frac])
        self.hi = np.array([float(sp_max), float(cell["batch_max"]),
                            hi_frac, hi_frac, hi_frac])
        self.canonical = np.array([
            [0.0, 1.0, lo_frac, lo_frac, lo_frac],
            [sp_max / 2, 1.0, 0.5, 0.5, 0.5],
            [float(sp_max), 1.0, hi_frac, hi_frac, hi_frac]]).astype(ftype)
        self.memo: dict[tuple, float] = {}

    def key(self, p) -> tuple:
        r, d = to_rav(p), self.s["memo_decimals"]
        return (r["sp"], r["batch"], round(r["dsp_frac"], d),
                round(r["bram_frac"], d), round(r["bw_frac"], d))

    def fitness(self, block) -> np.ndarray:
        out = []
        for p in block:
            k = self.key(p)
            if k not in self.memo:
                obj = fpga.evaluate(self.layers, self.part, to_rav(p),
                                    self.bits, self.bits, self.f)
                self.memo[k] = obj["throughput_ips"] if obj["feasible"] \
                    else 0.0
            out.append(self.memo[k])
        return np.array(out)

    def survivors(self, pos, fits) -> np.ndarray:
        rows, seen = [], set()
        for p in self.canonical:
            if self.key(p) not in seen:
                seen.add(self.key(p))
                rows.append(p)
        cap = self.s["survivors"] + len(rows)
        for i in np.argsort(-fits, kind="stable"):
            if len(rows) >= cap:
                break
            if self.key(pos[i]) in seen:
                continue
            seen.add(self.key(pos[i]))
            rows.append(pos[i])
        return np.array(rows)

    def run(self) -> dict:
        """``rav``, ``fitness``, ``screened``, ``evaluations`` (distinct
        RAVs at the memo resolution) and ``iterations`` (of the
        swarm)."""
        f, lo, hi = self.f, self.lo.astype(self.f), self.hi.astype(self.f)
        rng = np.random.default_rng(self.seed)
        pos = rng.uniform(self.lo, self.hi,
                          size=(self.screen, 5)).astype(f)
        pos[:3] = self.canonical
        fits = np.asarray(fpga.screen(
            self.layers, self.part, pos, self.bits, self.bits, ftype=f,
            itype=np.int64 if f == np.float64 else np.int32), np.float64)
        promoted = self.survivors(pos, fits)
        fits = self.fitness(promoted)
        i = int(np.argmax(fits))
        best_pos, best_fit = promoted[i].copy(), float(fits[i])

        # the swarm, seeded with the ranked survivors
        pop = self.population
        rng = np.random.default_rng(self.seed + 1)
        x = rng.uniform(self.lo, self.hi, size=(pop, 5)).astype(f)
        seeds = promoted[np.argsort(-fits, kind="stable")[:pop]]
        x[:len(seeds)] = seeds
        v = (rng.uniform(-1, 1, size=(pop, 5))
             * (self.hi - self.lo) * 0.1).astype(f)
        fx = self.fitness(x)
        pbest, pbest_fit = x.copy(), fx
        g = int(np.argmax(fx))
        g_pos, g_fit = pbest[g].copy(), float(fx[g])
        if g_fit > best_fit:
            best_pos, best_fit = g_pos.copy(), g_fit
        w, c1, c2 = (f(self.s[k]) for k in ("inertia", "c_local",
                                            "c_global"))
        stale, it = 0, 0
        while it < self.iterations:
            r1 = rng.random((pop, 5)).astype(f)
            r2 = rng.random((pop, 5)).astype(f)
            v = (w * v + c1 * r1 * (pbest - x)
                 + c2 * r2 * (g_pos[None, :] - x)).astype(f)
            x = np.clip(x + v, lo, hi)
            fx = self.fitness(x)
            better = fx > pbest_fit
            pbest = np.where(better[:, None], x, pbest)
            pbest_fit = np.where(better, fx, pbest_fit)
            b = int(np.argmax(fx))
            improved = bool(fx[b] > g_fit)
            if improved:
                g_pos, g_fit = x[b].copy(), float(fx[b])
            if g_fit > best_fit:
                best_pos, best_fit = g_pos.copy(), g_fit
            it += 1
            stale = 0 if improved else stale + 1
            if stale >= self.s["patience"]:
                break
        return dict(rav=to_rav(best_pos), fitness=best_fit,
                    screened=self.screen, evaluations=len(self.memo),
                    iterations=it)
