"""Tests for repro.obs and its threading through the DSE stack:
tracer/span semantics, the current tracer, deterministic sidecar merging,
schema validation, Chrome export, the spawn-pool campaign integration
(span nesting across process boundaries), the campaign's spans on a
profiler trace, store corrupt-line accounting, convergence traces riding
resume, and the committed example health report's drift check.
"""
import glob
import json
import os
import subprocess
import sys
import warnings
from pathlib import Path

import pytest

from repro.dse import (ResultStore, expand_cells, get_backend, run_campaign)
from repro.dse.obs import (events_for_store, example_health_md,
                           main as obs_main)
from repro.dse.report import (fixture_events, fixture_records,
                              health_section, render_report)
from repro.obs import trace as obs_trace
from repro.obs import (EVENTS_SCHEMA_VERSION, NULL, NullTracer, Tracer,
                       campaign_wall, chrome_path_for, chrome_trace,
                       counter_totals, current, events_dir_for,
                       events_path_for,
                       load_events, merge_events, slowest_spans, span_totals,
                       validate_events, worker_tracer, worker_utilization)

_FAST = dict(population=6, iterations=4)


def _tpu_cells():
    be = get_backend("tpu")
    return be, be.expand_cells(archs=["xlstm-350m"], shapes=["train_4k"],
                               chips=[8, 16], remats=["full"],
                               microbatches=[1, 2])


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_null_tracer_is_total_noop(tmp_path):
    n = NullTracer()
    assert not n.enabled and not NULL.enabled
    with n.span("anything", k=1):
        n.count("c", 3)
        n.gauge("g", 0.5)
    n.span_at("q", 0.0, 1.0)
    with n:
        pass
    n.close()
    assert list(tmp_path.iterdir()) == []  # nothing ever touches disk


def test_tracer_emits_nested_spans_and_counters(tmp_path):
    p = tmp_path / "t.jsonl"
    with Tracer(p, proc="main") as tr:
        with tr.span("outer", cell="x"):
            with tr.span("inner"):
                tr.count("hits", 2)
                tr.count("hits", 3)
            tr.gauge("load", 0.5)
    evs = load_events(p)
    assert validate_events(evs) == []
    assert all(e["schema"] == EVENTS_SCHEMA_VERSION for e in evs)
    by_name = {e["name"]: e for e in evs if e["kind"] == "span"}
    # inner closes first, at depth 1; outer wraps it at depth 0
    assert by_name["inner"]["depth"] == 1
    assert by_name["outer"]["depth"] == 0
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"]
    assert by_name["outer"]["ts"] <= by_name["inner"]["ts"]
    assert by_name["outer"]["attrs"] == {"cell": "x"}
    assert counter_totals(evs) == {"hits": 5}
    assert tr.counters == {"hits": 5}
    # per-process seq is a total order
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def test_current_tracer_is_the_one_entered(tmp_path):
    assert current() is NULL
    with Tracer(tmp_path / "a.jsonl") as outer:
        assert current() is outer
        with worker_tracer(tmp_path, proc="worker-1") as inner:
            assert current() is inner
            with current().span("deep"):
                pass
        assert current() is outer
    assert current() is NULL
    assert [e["name"] for e in load_events(tmp_path / "worker-1.jsonl")] \
        == ["deep"]
    with NULL:                  # the disabled tracer changes nothing
        assert current() is NULL


def test_tracers_import_nothing_for_annotations(tmp_path):
    """A worker's tracer never annotates; no tracer imports JAX to do so
    (a process without JAX can be running no profiler trace)."""
    code = ("import sys\n"
            "from repro.obs import Tracer, worker_tracer\n"
            "with worker_tracer(sys.argv[1]) as w, "
            "Tracer(sys.argv[1] + '/main.jsonl') as m:\n"
            "    assert w._annotation is None and m._annotation is None\n"
            "    with w.span('a'), m.span('b'):\n"
            "        pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(Path(__file__).resolve().parents[1] / "src"),
         os.environ.get("PYTHONPATH", "")]))
    subprocess.run([sys.executable, "-c", code, str(tmp_path)], env=env,
                   check=True, timeout=120)
    import jax  # noqa: F401 - loaded: the main tracer annotates
    assert Tracer(tmp_path / "m.jsonl")._annotation is not None
    assert worker_tracer(tmp_path, proc="w")._annotation is None


@pytest.mark.parametrize("every,on_disk", [(0.0, 2), (3600.0, 0)],
                         ids=["flush_due", "buffered"])
def test_events_reach_the_file_when_a_flush_is_due(tmp_path, monkeypatch,
                                                   every, on_disk):
    """Events are written when a flush is due, else on close: spans in a
    hot loop cost no write each."""
    monkeypatch.setattr(obs_trace, "FLUSH_EVERY_S", every)
    p = tmp_path / "t.jsonl"
    tr = Tracer(p)
    tr.count("a")
    with tr.span("b"):
        pass
    assert len(load_events(p)) == on_disk
    tr.close()
    assert [e["name"] for e in load_events(p)] == ["a", "b"]


def test_span_survives_exception(tmp_path):
    p = tmp_path / "t.jsonl"
    tr = Tracer(p)
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    tr.close()
    evs = load_events(p)
    assert [e["name"] for e in evs] == ["boom"]


def test_merge_events_is_deterministic(tmp_path):
    d = tmp_path / "ev"
    with Tracer(d / "main.jsonl", proc="main") as tr:
        tr.count("a")
    with worker_tracer(d, proc="worker-7") as tr:
        with tr.span("w"):
            pass
    m1 = merge_events(d, tmp_path / "m1.jsonl")
    m2 = merge_events(d, tmp_path / "m2.jsonl")
    assert m1 == m2
    assert (tmp_path / "m1.jsonl").read_text() == \
        (tmp_path / "m2.jsonl").read_text()
    # merged order is the canonical (ts, proc, seq) sort
    keys = [(e["ts"], e["proc"], e["seq"]) for e in m1]
    assert keys == sorted(keys)
    assert {e["proc"] for e in m1} == {"main", "worker-7"}
    # undecodable sidecar junk is skipped (with a warning), not fatal
    (d / "junk.jsonl").write_text("{not json\n\n")
    with pytest.warns(UserWarning, match="skipped 1 undecodable"):
        assert merge_events(d) == m1


def test_merge_events_tolerates_truncated_sidecar(tmp_path):
    """A worker killed mid-write (crash fault, SIGKILL) leaves a torn
    final line in its sidecar; the merge must keep every intact event
    and surface the loss instead of raising."""
    d = tmp_path / "ev"
    with worker_tracer(d, proc="worker-9") as tr:
        tr.count("a")
        tr.count("b")
    sidecar = next(d.glob("*.jsonl"))
    whole = sidecar.read_text().splitlines()
    torn = whole[0] + "\n" + whole[1][: len(whole[1]) // 2]
    sidecar.write_text(torn)                       # no trailing newline
    stats: dict = {}
    assert [e["name"] for e in load_events(sidecar, stats)] == ["a"]
    assert stats == {"skipped_lines": 1}
    with pytest.warns(UserWarning, match="skipped 1 undecodable"):
        merged = merge_events(d, tmp_path / "m.jsonl")
    assert [e["name"] for e in merged] == ["a"]


def test_validate_events_flags_bad_shapes():
    good = fixture_events()
    assert validate_events(good) == []
    bad = [dict(good[0], schema=99),
           dict(good[0], kind="nope"),
           {k: v for k, v in good[1].items() if k != "ts"},
           dict(good[0], dur="fast")]
    problems = validate_events(bad)
    assert len(problems) == 4


def test_aggregations_on_fixture_events():
    evs = fixture_events()
    assert campaign_wall(evs) == pytest.approx(6.65)
    totals = span_totals(evs)
    assert totals["cell.eval"].count == 2
    assert totals["cell.eval"].max_s == pytest.approx(5.8)
    util = worker_utilization(evs)
    assert set(util) == {"worker-1", "worker-2"}
    assert util["worker-2"]["util"] == pytest.approx(5.8 / 6.65)
    slow = slowest_spans(evs, k=1)
    assert len(slow) == 1 and "zcu102" in slow[0]["attrs"]["cell"]


def test_chrome_trace_structure():
    evs = fixture_events()
    doc = chrome_trace(evs)
    json.dumps(doc)  # exportable
    tes = doc["traceEvents"]
    names = {e["args"]["name"] for e in tes if e["ph"] == "M"}
    assert names == {"main", "worker-1", "worker-2"}
    xs = [e for e in tes if e["ph"] == "X"]
    assert len(xs) == len([e for e in evs if e["kind"] == "span"])
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    # counter samples accumulate; gauges sample raw values
    cs = [e for e in tes if e["ph"] == "C"]
    done = [e["args"]["cells.done"] for e in cs
            if e["name"] == "cells.done"]
    assert done == [1, 2]
    assert chrome_trace([]) == {"traceEvents": [],
                                "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# campaign integration (spawn pool)
# ---------------------------------------------------------------------------


def test_traced_campaign_spawn_pool(tmp_path):
    be, cells = _tpu_cells()
    store = tmp_path / "t.jsonl"
    rep = run_campaign(cells, str(store), backend=be, workers=2, trace=True)
    assert rep.events_path == events_path_for(store)
    assert rep.events_path.exists()
    assert not chrome_path_for(store).exists()   # exported on demand only
    evs = load_events(rep.events_path)
    assert validate_events(evs) == []
    # span nesting survived pickling into spawn workers: every cell got
    # a queue.wait + cell.run(depth 0) wrapping cell.eval(depth 1), all
    # attributed to a worker proc, not main
    for name, depth in (("queue.wait", 0), ("cell.run", 0),
                        ("cell.eval", 1)):
        got = [e for e in evs if e.get("name") == name]
        assert len(got) == len(cells)
        assert all(e["depth"] == depth for e in got)
        assert all(e["proc"].startswith("worker-") for e in got)
    appends = [e for e in evs if e.get("name") == "store.append"]
    assert len(appends) == len(cells)
    assert all(e["proc"] == "main" for e in appends)
    assert counter_totals(evs)["cells.done"] == len(cells)
    assert max(e["value"] for e in evs
               if e.get("name") == "pool.inflight") <= len(cells)
    # the obs CLI reads the same store
    assert events_for_store(str(store)) == evs
    rc = obs_main([str(store), "--validate",
                   "--chrome", str(tmp_path / "c.json")])
    assert rc == 0
    json.loads((tmp_path / "c.json").read_text())


def test_untraced_campaign_emits_zero_telemetry_files(tmp_path):
    be, cells = _tpu_cells()
    store = tmp_path / "t.jsonl"
    rep = run_campaign(cells, str(store), backend=be, workers=2)
    assert rep.events_path is None
    assert not events_dir_for(store).exists()
    assert not events_path_for(store).exists()
    assert not chrome_path_for(store).exists()
    assert sorted(x.name for x in tmp_path.iterdir()) == ["t.jsonl"]
    assert events_for_store(str(store)) == []


PROGRAM_SPANS = ("campaign", "screen.jax", "screen.tables", "screen.call",
                 "cell.run", "cell.eval", "search.full_eval", "store.append")


def _host_spans(logdir) -> list[tuple[str, str, float, float]]:
    """``(line, name, start_ns, end_ns)`` of the program's spans on the
    host plane of the profiler trace written under ``logdir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return [(line.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for ev in line.events
            if ev.name in PROGRAM_SPANS]


@pytest.mark.parametrize("traced", [True, False],
                         ids=["traced", "untraced"])
def test_campaign_spans_land_on_the_profiler_trace(tmp_path, traced):
    """Under a profiler trace, a traced campaign's own spans are host
    annotations nested inside ``campaign``, the screen split into its
    tables and its device call; an untraced one adds none and writes
    nothing beside its store."""
    import jax
    cells = expand_cells(["vgg16"], [(64, 64), (96, 96)], ["zc706"], [16],
                         [1])
    store = tmp_path / "run" / "c.jsonl"
    logdir = tmp_path / "profile"
    jax.profiler.start_trace(str(logdir))
    try:
        rep = run_campaign(cells, str(store), trace=traced, **_FAST,
                           searcher="hyperband",
                           searcher_config={"screen": 64, "survivors": 4},
                           jax_screen=True, install_signal_handlers=False)
    finally:
        jax.profiler.stop_trace()
    assert rep.new_cells == len(cells)
    host = _host_spans(logdir)
    if not traced:
        assert host == []
        assert sorted(x.name for x in store.parent.iterdir()) == ["c.jsonl"]
        return
    (line, _, lo, hi), = [h for h in host if h[1] == "campaign"]
    counts = {n: sum(h[1] == n for h in host) for n in PROGRAM_SPANS}
    assert counts["screen.tables"] == counts["screen.call"] == 1
    assert counts["store.append"] == counts["cell.eval"] == len(cells)
    assert counts["search.full_eval"] >= len(cells)
    assert all(h[0] == line and lo <= h[2] <= h[3] <= hi for h in host)
    # the same spans in the events file, the screen's two inside it
    evs = load_events(rep.events_path)
    assert validate_events(evs) == []
    depth = {e["name"]: e["depth"] for e in evs if e["kind"] == "span"}
    assert depth["screen.tables"] == depth["screen.call"] \
        == depth["screen.jax"] + 1 == depth["campaign"] + 2
    evals = [e for e in evs if e.get("name") == "search.full_eval"]
    assert len(evals) == counts["search.full_eval"]
    assert all(e["attrs"]["ravs"] > 0 for e in evals)
    assert "screen.jax_cells" not in counter_totals(evs)


def test_trace_field_roundtrips_resume(tmp_path):
    store = tmp_path / "c.jsonl"
    cells = expand_cells(["vgg16"], [(64, 64)], ["zc706"], [16], [1])
    r1 = run_campaign(cells, str(store), trace=True, **_FAST)
    t = r1.records[0]["trace"]
    assert t["schema"] == 1 and t["engine"] == "pso"
    assert t["stop_reason"] in ("converged", "iteration_cap")
    assert t["iterations"] <= _FAST["iterations"]
    assert len(t["history"]) == t["iterations"] + 1  # init + per-iteration
    assert t["best_fitness"] == pytest.approx(max(t["history"]))
    # a traced store resumes cleanly in an untraced re-run (and vice
    # versa): the trace field is additive and search-config matching
    # does not see it
    r2 = run_campaign(cells, str(store), **_FAST)
    assert r2.new_cells == 0 and r2.reused_cells == len(cells)
    assert r2.records[0]["trace"] == t
    # and the reloaded record round-trips through JSONL byte-identically
    assert ResultStore(store).get(cells[0].key)["trace"] == t


def test_enumeration_trace_on_tpu_records(tmp_path):
    be, cells = _tpu_cells()
    rep = run_campaign(cells, str(tmp_path / "t.jsonl"), backend=be)
    for rec in rep.records:
        t = rec["trace"]
        assert t["engine"] == "enumeration"
        assert t["stop_reason"] == "exhaustive"
        assert t["iterations"] == t["evaluations"] > 0


# ---------------------------------------------------------------------------
# store corruption accounting
# ---------------------------------------------------------------------------


def test_store_torn_final_line_is_benign(tmp_path):
    p = tmp_path / "s.jsonl"
    s = ResultStore(p)
    s.put({"cell_key": "a", "x": 1})
    with p.open("a") as f:
        f.write('{"cell_key": "b", "x":')
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any warning -> failure
        s2 = ResultStore(p)
    assert s2.skipped_lines == 1
    assert s2.corrupt_lines == 0


def test_store_mid_file_corruption_warns_and_counts(tmp_path):
    p = tmp_path / "s.jsonl"
    s = ResultStore(p)
    s.put({"cell_key": "a", "x": 1})
    s.put({"cell_key": "b", "x": 2})
    lines = p.read_text().splitlines()
    lines[0] = lines[0][:10]  # damage a NON-final line
    p.write_text("\n".join(lines) + "\n")
    tr = Tracer(tmp_path / "ev" / "main.jsonl")
    with pytest.warns(RuntimeWarning, match="corrupt"):
        s2 = ResultStore(p, tracer=tr)
    tr.close()
    assert s2.skipped_lines == 1
    assert s2.corrupt_lines == 1
    assert "a" not in s2 and s2.get("b")["x"] == 2
    evs = load_events(tmp_path / "ev" / "main.jsonl")
    assert counter_totals(evs)["store.corrupt_lines"] == 1


# ---------------------------------------------------------------------------
# health report + committed example drift check
# ---------------------------------------------------------------------------


def test_health_section_flags_iteration_capped_cells():
    md = "\n".join(health_section(fixture_records(), fixture_events()))
    assert "Wall-time breakdown" in md
    assert "Worker utilization" in md
    assert "Slowest cells" in md
    assert "Convergence diagnostics" in md
    assert "**iteration_cap**" in md
    assert "net=vgg16|in=224x224|fpga=ku115|prec=16|bmax=1" in md


def test_health_section_without_any_telemetry():
    recs = [{"cell_key": "x", "objectives": {"feasible": True}}]
    md = "\n".join(health_section(recs))
    assert "No telemetry" in md


def test_render_report_includes_health_only_when_telemetry():
    fix = fixture_records()
    assert "Campaign health" in render_report(fix)  # traces present
    ok = [r for r in fix if r.get("status", "ok") == "ok"]
    failed = [r for r in fix if r.get("status") == "failed"]
    bare = [dict(r) for r in ok]
    for r in bare:
        r.pop("trace", None)
        r.pop("resilience", None)
    assert "Campaign health" not in render_report(bare)
    assert "Campaign health" in render_report(bare,
                                              events=fixture_events())
    # a quarantined record alone is telemetry enough — failures must
    # never drop out of the report silently
    md = render_report(bare + failed)
    assert "Campaign health" in md and "Failures & retries" in md


def test_committed_example_health_report_is_current():
    committed = Path(__file__).resolve().parent.parent / \
        "docs" / "reports" / "example_health.md"
    assert committed.exists(), \
        "regenerate with: python -m repro.dse.obs --fixture --out " \
        "docs/reports/example_health.md"
    assert committed.read_text() == example_health_md(), \
        "docs/reports/example_health.md is stale — regenerate with: " \
        "python -m repro.dse.obs --fixture --out " \
        "docs/reports/example_health.md"


def test_obs_cli_fixture_mode(tmp_path, capsys):
    out = tmp_path / "ex.md"
    assert obs_main(["--fixture", "--out", str(out)]) == 0
    assert out.read_text() == example_health_md()
    assert obs_main(["--fixture"]) == 0
    assert "Campaign health" in capsys.readouterr().out
