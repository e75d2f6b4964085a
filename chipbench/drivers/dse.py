"""DSE cells: the system's campaign (``repro.dse.campaign.run_campaign``,
hyperband with the rung-0 screen on the device) run back to back over
one grid of campaign cells, each campaign into a fresh store, its base
seed drawn from the run's seed and the campaign's index.

Set-up runs one campaign, which compiles the screen's one shape and
fills the host's per-net tables. After the window every store is read
back and compared with the records the campaign handed it, a seeded
sample of records is re-evaluated by the plain reference model, the
search of a second seeded sample of records is run again by the plain
reference search from the campaign's base seed, and the device screen of a
seeded sample of campaigns is compared with the reference screen on the
same candidates.
"""
from __future__ import annotations

import contextlib
import json
import shutil
import time

import numpy as np

from chipbench import harness
from chipbench.reference import fpga as ref
from chipbench.reference import search as ref_search


class Cell:
    def __init__(self, spec: harness.CellSpec, seed: int, devices, peak):
        self.spec, self.seed = spec, seed
        self.cfg, self.tr = spec.config, spec.traffic
        self.dir = spec.root / "results" / "chipbench" / spec.name
        self.campaigns: list[dict] = []
        self.screens = harness.Reservoir(
            self.tr["sample_campaigns"],
            np.random.default_rng(harness.seed_words(seed, 3)))
        self._annotate = None
        self._screen_out = None
        self.store_s = 0.0

    def setup(self) -> None:
        from repro.core import screen_jax
        from repro.dse.campaign import expand_cells
        tr = self.tr
        self.grid = expand_cells(tr["nets"], [tuple(x) for x in tr["inputs"]],
                                 tr["fpgas"], tr["precisions"],
                                 tr["batch_caps"])
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        # observe the device screen's inputs and outputs as they pass
        program_screen = self._program_screen = screen_jax.screen_cells

        def screen_cells(stacked, positions):
            with self._annotate("bench.screen"):
                out = program_screen(stacked, positions)
            self._screen_out = (positions, out)
            return out
        screen_jax.screen_cells = screen_cells
        self._campaign("warm", harness.seed_words(self.seed, 11), None)

    def _campaign(self, tag, base_seed: int, annotate) -> dict:
        """One campaign into a fresh store; ``annotate`` is the profiler's
        annotation in a traced run, else None."""
        from repro.dse.campaign import run_campaign
        from repro.dse.store import open_store
        path = self.dir / f"{tag}.jsonl"
        store = open_store(str(path))
        put, durable = store.put, []
        traced, annotate = annotate is not None, annotate or _null

        def timed_put(rec):
            t = time.perf_counter()
            with annotate("bench.store_append"):
                put(rec)
            durable.append((time.perf_counter(), rec))
            self.store_s += durable[-1][0] - t
        store.put = timed_put
        self._annotate = annotate
        cfg = self.cfg
        t0 = time.perf_counter()
        with annotate("bench.campaign"):
            report = run_campaign(
                self.grid, store, base_seed=base_seed,
                population=cfg["population"], iterations=cfg["iterations"],
                workers=cfg["workers"], backend=cfg["backend"],
                searcher=cfg["searcher"],
                searcher_config=cfg["searcher_config"],
                jax_screen=cfg["jax_screen"], trace=traced,
                install_signal_handlers=False)
        return {"path": path, "t0": t0, "t1": time.perf_counter(),
                "base_seed": base_seed, "durable": durable,
                "events": report.events_path, "screen": self._screen_out}

    def run_window(self, seconds: float, annotate) -> None:
        self.store_s = 0.0
        t0 = time.perf_counter()
        self.deadline = t0 + seconds
        self.seconds = seconds
        i = 0
        while True:
            c = self._campaign(f"c{i}", harness.seed_words(self.seed, 10, i),
                               annotate)
            self.screens.offer((i, c.pop("screen")))
            self.campaigns.append(c)
            i += 1
            if time.perf_counter() >= self.deadline:
                break

    def _in_window(self):
        """``(latency s, record, campaign's base seed)`` of every record
        durable in the window."""
        return [(t - c["t0"], rec, c["base_seed"]) for c in self.campaigns
                for t, rec in c["durable"] if t <= self.deadline]

    def end_to_end(self) -> dict:
        done = self._in_window()
        return {"dse_cells_per_s": harness.rate(len(done), self.seconds),
                "dse_result_p95_s": harness.percentile(
                    [lat for lat, _, _ in done], 95)}

    def window_split(self) -> dict:
        """Seconds of the window inside campaigns, in their stores' appends
        and between campaigns, and the campaigns' mean and slowest wall
        time."""
        walls = [c["t1"] - c["t0"] for c in self.campaigns]
        return {"campaigns": len(walls), "in_campaigns_s": sum(walls),
                "store_s": self.store_s,
                "between_s": self.campaigns[-1]["t1"]
                - self.campaigns[0]["t0"] - sum(walls),
                "mean_campaign_s": sum(walls) / len(walls),
                "max_campaign_s": max(walls)}

    def context(self) -> dict:
        spans: dict[str, list[float]] = {"screen.jax": [], "store.append": []}
        for c in self.campaigns:
            if c["events"] is None:
                continue
            with open(c["events"]) as f:
                for line in f:
                    ev = json.loads(line)
                    if ev.get("kind") == "span" and ev["name"] in spans:
                        spans[ev["name"]].append(ev["dur"])
        return {"spans": spans,
                "search_s": [rec["search_time_s"]
                             for _, rec, _ in self._in_window()
                             if "search_time_s" in rec]}

    def attempted_failed(self) -> tuple[int, int]:
        recs = [rec for c in self.campaigns for _, rec in c["durable"]]
        failed = sum(rec.get("status", "ok") != "ok" for rec in recs)
        return len(self.grid) * len(self.campaigns), failed

    def release(self) -> None:
        from repro.core import screen_jax
        screen_jax.screen_cells = self._program_screen
        self._annotate = None

    # -- the comparison that decides `correct` ------------------------------

    def _layers(self, cell: dict):
        return ref.net_layers(self.cfg, cell["net"], cell["h"], cell["w"])

    def _part(self, name: str) -> ref.Part:
        return ref.Part(**self.cfg["parts"][name])

    def store_lost(self) -> int:
        """Records handed to a store that do not read back from its file
        byte for byte, plus grid cells a campaign gave no record."""
        lost = 0
        for c in self.campaigns:
            stored = {}
            with open(c["path"]) as f:
                for line in f:
                    rec = json.loads(line)
                    stored[rec["cell_key"]] = line.rstrip("\n")
            handed = {rec["cell_key"]: json.dumps(rec, sort_keys=True)
                      for _, rec in c["durable"]}
            lost += sum(stored.get(k) != v for k, v in handed.items())
            lost += len(self.grid) - len(handed)
        return lost

    def record_error(self, records, ftype=float) -> float:
        """Worst relative gap between a record's objectives and the
        reference model's evaluation of the record's RAV; a feasibility
        flag that differs counts as 1."""
        worst = 0.0
        for rec in records:
            cell = rec["cell"]
            want = ref.evaluate(self._layers(cell), self._part(cell["fpga"]),
                                rec["rav"], cell["precision"],
                                cell["precision"], ftype)
            got = rec["objectives"]
            for k in ref.OBJECTIVES:
                if k == "feasible":
                    gap = float(got[k] != want[k])
                else:
                    gap = abs(got[k] - want[k]) / max(abs(want[k]), 1e-300)
                if not gap <= worst:
                    worst = gap
        return worst

    def screen_off_share(self, ftype=np.float64, xp=np,
                         itype=np.int64) -> float:
        """Share of the sampled campaigns' screened candidates whose
        device value departs from the reference screen on the same
        candidates by more than 1e-9, relative."""
        off, n = 0, 0
        for _, (positions, out) in self.screens.items:
            for cell, pos, got in zip(self.grid, positions, out):
                want = np.asarray(ref.screen(
                    self._layers({"net": cell.net, "h": cell.h,
                                  "w": cell.w}),
                    self._part(cell.fpga), pos, cell.precision,
                    cell.precision, xp=xp, ftype=ftype, itype=itype),
                    np.float64)
                gap = np.abs(np.asarray(got, np.float64) - want) \
                    / np.maximum(np.abs(want), 1e-300)
                off += int(np.sum(~(gap <= 1e-9)))
                n += gap.size
        return off / max(n, 1)

    def search_off_share(self, sample, ftype=np.float64) -> float:
        """Share of the sampled records whose search is not the one the
        plain reference search makes for the record's cell from its
        campaign's base seed: another RAV, or another count of screened
        candidates, of full evaluations or of swarm iterations."""
        off = 0
        for rec, base_seed in sample:
            cell = rec["cell"]
            want = ref_search.Search(
                self.cfg, self._layers(cell), self._part(cell["fpga"]), cell,
                base_seed, ftype).run()
            got = dict(rav=rec["rav"], evaluations=rec["evaluations"],
                       iterations=rec["iterations"],
                       screened=rec["trace"].get("screened", 0))
            off += any(got[k] != want[k] for k in got)
        return off / max(len(sample), 1)

    def control(self) -> dict:
        """The compared numbers with the reference in float32 in the
        program's place: the screen on the device, the model and the
        search on the host."""
        import jax.numpy as jnp
        return {"record_max_rel_err": self.record_error(
                    [rec for rec, _ in self.sampled_records()], np.float32),
                "search_off_share": self.search_off_share(
                    self.sampled_searches(), np.float32),
                "screen_off_share": self.screen_off_share(
                    jnp.float32, jnp, jnp.int32)}

    def _sample(self, size: int, salt: int) -> list[tuple[dict, int]]:
        """``(record, base seed)`` of a seeded sample of the window's
        records."""
        recs = [(rec, base) for _, rec, base in self._in_window()
                if rec.get("status", "ok") == "ok"]
        rng = np.random.default_rng(harness.seed_words(self.seed, salt))
        k = min(size, len(recs))
        return [recs[i] for i in sorted(rng.choice(len(recs), k,
                                                   replace=False))]

    def sampled_records(self) -> list[tuple[dict, int]]:
        return self._sample(self.tr["sample_records"], 4)

    def sampled_searches(self) -> list[tuple[dict, int]]:
        return self._sample(self.tr["sample_searches"], 5)

    def checks(self) -> list[harness.Check]:
        lim = self.tr["limits"]
        return [
            harness.Check("store_lost", float(self.store_lost()),
                          lim["store_lost"]),
            harness.Check("record_max_rel_err", self.record_error(
                [rec for rec, _ in self.sampled_records()]),
                          lim["record_max_rel_err"]),
            harness.Check("search_off_share", self.search_off_share(
                self.sampled_searches()), lim["search_off_share"]),
            harness.Check("screen_off_share", self.screen_off_share(),
                          lim["screen_off_share"]),
        ]


def _null(_name: str):
    return contextlib.nullcontext()
