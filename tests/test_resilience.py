"""Resilience-layer tests: retry policy, fault-injection harness,
quarantine semantics, pool crash/timeout recovery, signal-driven partial
campaigns, and the explicit non-ok filtering every downstream consumer
(report, frontier, placement) must apply.

Everything nondeterministic about real failures (which cell, which
attempt, how long) is pinned by :mod:`repro.testing.faults`, so these
tests never rely on races or wall-clock flakiness. The pool tests spawn
real worker processes — they are the point — but keep the grids tiny.
"""
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from repro.dse.backends import run_cell_by_backend
from repro.dse.campaign import expand_cells, run_campaign
from repro.dse.cli import exit_code
from repro.dse.placement import candidates_by_workload, pooled_records
from repro.dse.report import render_report
from repro.dse.resilience import (CellTimeout, CorruptRecord, RetryPolicy,
                                  WorkerCrash, attempt_outcome, execute_cell,
                                  quarantine_record, validate_record)
from repro.dse.store import is_ok, open_store, record_status
from repro.testing.faults import (ENV_VAR, Fault, FaultPlan,
                                  InjectedPermanentError,
                                  InjectedTransientError, load_plan)

FAST = dict(population=4, iterations=2, progress=None)
CELLS2 = expand_cells(["alexnet"], [(224, 224)], ["ku115", "zcu102"],
                      [16], [1])
KU115_KEY = "net=alexnet|in=native|fpga=ku115|prec=16|bmax=1"


def scrub(rec):
    """Volatile fields removed: timing and retry metadata — everything
    else must be bit-identical between faulted and fault-free runs."""
    return {k: v for k, v in rec.items()
            if k not in ("search_time_s", "resilience")}


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------


def test_backoff_is_deterministic_exponential_and_jittered():
    p = RetryPolicy(backoff_s=0.1, backoff_factor=2.0, jitter_frac=0.1)
    d1 = p.backoff("cell-a", 1)
    assert d1 == p.backoff("cell-a", 1)              # reproducible
    assert d1 != p.backoff("cell-b", 1)              # de-synchronized
    assert d1 != RetryPolicy(backoff_s=0.1, seed=7).backoff("cell-a", 1)
    for attempt in (1, 2, 3):
        base = 0.1 * 2.0 ** (attempt - 1)
        d = p.backoff("cell-a", attempt)
        assert base * 0.9 <= d <= base * 1.1         # jitter bounded


def test_policy_validation():
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(cell_timeout_s=0.0)


def test_failure_taxonomy():
    p = RetryPolicy()
    assert p.retryable(RuntimeError("flaky"))
    assert p.retryable(CellTimeout())
    assert p.retryable(WorkerCrash())
    assert p.retryable(CorruptRecord("torn"))
    assert p.retryable(InjectedTransientError("x"))
    for exc in (ValueError("bad"), KeyError("k"), TypeError("t"),
                ZeroDivisionError(), InjectedPermanentError("x")):
        assert not p.retryable(exc)
    assert attempt_outcome(CellTimeout()) == "timeout"
    assert attempt_outcome(WorkerCrash()) == "crash"
    assert attempt_outcome(CorruptRecord("x")) == "corrupt"
    assert attempt_outcome(RuntimeError()) == "error"


def test_validate_record_rejects_garbage():
    cell = CELLS2[0]
    with pytest.raises(CorruptRecord):
        validate_record(cell, None)
    with pytest.raises(CorruptRecord):
        validate_record(cell, {"cell_key": "someone-else"})
    with pytest.raises(CorruptRecord):
        validate_record(cell, {"cell_key": cell.key})   # no objectives
    validate_record(cell, {"cell_key": cell.key,
                           "objectives": {"feasible": True}})


# ---------------------------------------------------------------------------
# execute_cell (the shared single-worker primitive)
# ---------------------------------------------------------------------------


def _flaky_fn(fail_attempts, exc_type=RuntimeError):
    def attempt_fn(cell, attempt):
        if attempt in fail_attempts:
            raise exc_type(f"boom on {attempt}")
        return {"cell_key": cell.key, "objectives": {"feasible": True},
                "evaluations": 1}
    return attempt_fn


def test_execute_cell_retries_transient_then_stamps():
    out = execute_cell(CELLS2[0], _flaky_fn({1}),
                       RetryPolicy(backoff_s=0.0), sleep=lambda s: None)
    assert out.ok and out.retried and not out.failed
    res = out.record["resilience"]
    assert res["attempts"] == 2 and res["retries"] == 1
    assert [a["outcome"] for a in res["attempt_log"]] == ["error", "ok"]


def test_execute_cell_first_attempt_success_is_unstamped():
    out = execute_cell(CELLS2[0], _flaky_fn(set()))
    assert out.ok and not out.retried
    assert "resilience" not in out.record


def test_execute_cell_permanent_failure_never_retries():
    calls = []

    def attempt_fn(cell, attempt):
        calls.append(attempt)
        raise InjectedPermanentError("deterministic model bug")

    out = execute_cell(CELLS2[0], attempt_fn,
                       RetryPolicy(max_attempts=5, backoff_s=0.0),
                       search={"base_seed": 0})
    assert calls == [1]                       # one attempt, no retry
    assert out.failed
    rec = out.record
    assert record_status(rec) == "failed" and not is_ok(rec)
    assert rec["error_type"] == "InjectedPermanentError"
    assert rec["attempts"] == 1 and rec["evaluations"] == 0
    assert "deterministic model bug" in rec["error"]
    assert "backend" not in rec               # fpga convention


def test_execute_cell_exhausts_budget_then_quarantines():
    out = execute_cell(CELLS2[0], _flaky_fn({1, 2, 3}),
                       RetryPolicy(max_attempts=3, backoff_s=0.0),
                       sleep=lambda s: None)
    assert out.failed and out.record["attempts"] == 3
    assert [a["outcome"] for a in out.record["attempt_log"]] \
        == ["error"] * 3


def test_quarantine_record_backend_field_convention():
    err = RuntimeError("x")
    log = [{"attempt": 1, "outcome": "error", "duration_s": 0.1,
            "error_type": "RuntimeError"}]
    assert "backend" not in quarantine_record(
        CELLS2[0], search=None, error=err, attempt_log=log)
    assert quarantine_record(CELLS2[0], search=None, error=err,
                             attempt_log=log, backend="tpu")["backend"] \
        == "tpu"


# ---------------------------------------------------------------------------
# fault-injection harness
# ---------------------------------------------------------------------------


def test_fault_plan_round_trip(tmp_path):
    plan = FaultPlan({"a": Fault("raise-transient", (1, 2)),
                      "b": Fault("hang-for", (), hang_s=1.5)})
    p = plan.save(tmp_path / "plan.json")
    loaded = load_plan(p)
    assert loaded == plan
    assert load_plan(plan.as_dict()) == plan
    assert load_plan(plan) is plan


def test_fault_fires_on_listed_attempts_only():
    f = Fault("raise-transient", (2,))
    assert not f.fires_on(1) and f.fires_on(2) and not f.fires_on(3)
    assert Fault("raise-transient", ()).fires_on(99)   # empty = always
    with pytest.raises(ValueError):
        Fault("set-on-fire")


def test_seeded_plan_is_deterministic():
    keys = [f"cell-{i}" for i in range(64)]
    a = FaultPlan.seeded(keys, seed=3, rate=0.25)
    b = FaultPlan.seeded(list(reversed(keys)), seed=3, rate=0.25)
    assert a == b                              # order-independent
    assert 0 < len(a.faults) < len(keys)       # rate actually selects
    assert FaultPlan.seeded(keys, seed=4, rate=0.25) != a


def test_mangle_after_strips_objectives():
    plan = FaultPlan({"k": Fault("corrupt-record")})
    rec = {"cell_key": "k", "objectives": {"feasible": True}}
    bad = plan.mangle_after("k", 1, rec)
    assert "objectives" not in bad and bad["injected_corruption"]
    assert plan.mangle_after("k", 2, rec) is rec        # attempt 2 clean
    assert plan.mangle_after("other", 1, rec) is rec


def test_harness_env_var_arms_run_cell_by_backend(tmp_path, monkeypatch):
    plan = FaultPlan({CELLS2[0].key: Fault("raise-permanent")})
    monkeypatch.setenv(ENV_VAR, str(plan.save(tmp_path / "p.json")))
    with pytest.raises(InjectedPermanentError):
        run_cell_by_backend("fpga", CELLS2[0], 0, 4, 2, None, None)
    # attempt 2 is past the fault's window: evaluation goes through
    rec = run_cell_by_backend("fpga", CELLS2[0], 0, 4, 2, None, None,
                              attempt=2)
    assert rec["cell_key"] == CELLS2[0].key
    # unarmed: same call, no fault module in the loop
    monkeypatch.delenv(ENV_VAR)
    assert run_cell_by_backend("fpga", CELLS2[0], 0, 4, 2, None,
                               None)["cell_key"] == CELLS2[0].key


# ---------------------------------------------------------------------------
# serial campaigns under faults
# ---------------------------------------------------------------------------


def test_transient_fault_retries_to_byte_identical_record(tmp_path,
                                                          monkeypatch):
    clean = run_campaign(CELLS2, str(tmp_path / "clean.jsonl"), **FAST)
    plan = FaultPlan({KU115_KEY: Fault("raise-transient", (1,))})
    monkeypatch.setenv(ENV_VAR, str(plan.save(tmp_path / "p.json")))
    faulted = run_campaign(CELLS2, str(tmp_path / "faulted.jsonl"),
                           policy=RetryPolicy(backoff_s=0.001), **FAST)
    assert not faulted.partial and exit_code(faulted) == 0
    by_key = {r["cell_key"]: r for r in faulted.records}
    assert by_key[KU115_KEY]["resilience"]["retries"] == 1
    for cr, fr in zip(clean.records, faulted.records):
        assert scrub(cr) == scrub(fr)   # retry converged to same answer


def test_corrupt_record_fault_is_caught_and_retried(tmp_path, monkeypatch):
    plan = FaultPlan({KU115_KEY: Fault("corrupt-record", (1,))})
    monkeypatch.setenv(ENV_VAR, str(plan.save(tmp_path / "p.json")))
    report = run_campaign(CELLS2, str(tmp_path / "s.jsonl"),
                          policy=RetryPolicy(backoff_s=0.001), **FAST)
    assert not report.partial
    rec = {r["cell_key"]: r for r in report.records}[KU115_KEY]
    assert rec["resilience"]["attempt_log"][0]["outcome"] == "corrupt"
    assert "injected_corruption" not in rec


def test_permanent_fault_quarantines_without_aborting_others(tmp_path,
                                                             monkeypatch):
    store = tmp_path / "s.jsonl"
    plan = FaultPlan({KU115_KEY: Fault("raise-permanent")})
    monkeypatch.setenv(ENV_VAR, str(plan.save(tmp_path / "p.json")))
    report = run_campaign(CELLS2, str(store), **FAST)
    assert report.partial and report.failed_cells == 1
    assert exit_code(report) == 3
    assert len(report.records) == 2           # other cell completed
    assert len(report.failures()) == 1
    assert len(report.feasible()) == 1        # failed record filtered
    fkeys = {json.loads(line)["cell_key"] for line in store.open()
             if json.loads(line).get("status") == "failed"}
    assert fkeys == {KU115_KEY}

    # resume WITHOUT --retry-failed: quarantine is sticky, fault or not
    monkeypatch.delenv(ENV_VAR)
    r2 = run_campaign(CELLS2, str(store), **FAST)
    assert r2.new_cells == 0 and r2.failed_cells == 1

    # resume WITH retry_failed and the fault gone: cell goes green
    r3 = run_campaign(CELLS2, str(store), retry_failed=True, **FAST)
    assert r3.new_cells == 1 and r3.failed_cells == 0
    assert not r3.partial and exit_code(r3) == 0
    # last-wins: the success superseded the quarantine record
    assert is_ok(open_store(str(store)).get(KU115_KEY))


def test_deeper_search_config_rerun_retries_quarantined_cell(
        tmp_path, monkeypatch):
    store = tmp_path / "s.jsonl"
    plan = FaultPlan({KU115_KEY: Fault("raise-permanent")})
    monkeypatch.setenv(ENV_VAR, str(plan.save(tmp_path / "p.json")))
    run_campaign(CELLS2, str(store), **FAST)
    monkeypatch.delenv(ENV_VAR)
    # a different search config is a different experiment: the failed
    # record no longer resume-matches, so the cell re-runs even without
    # retry_failed
    r = run_campaign(CELLS2, str(store), population=6, iterations=3,
                     progress=None)
    assert r.failed_cells == 0 and not r.partial


# ---------------------------------------------------------------------------
# non-ok records never leak into report / frontier / placement
# ---------------------------------------------------------------------------


def _quarantined(key=KU115_KEY):
    return {
        "schema": 1, "status": "failed", "quarantine_schema": 1,
        "cell_key": key,
        "cell": {"net": "alexnet", "h": 0, "w": 0, "fpga": "ku115",
                 "precision": 16, "batch_max": 1},
        "search": None, "error_type": "RuntimeError", "error": "boom",
        "attempts": 3,
        "attempt_log": [{"attempt": a, "outcome": "error",
                         "duration_s": 0.01, "error_type": "RuntimeError"}
                        for a in (1, 2, 3)],
        "evaluations": 0,
    }


def test_failed_records_excluded_from_every_consumer(tmp_path):
    report = run_campaign([CELLS2[1]], str(tmp_path / "s.jsonl"), **FAST)
    records = report.records + [_quarantined()]

    assert all(r["cell_key"] != KU115_KEY
               for r in candidates_by_workload(records, "tflops").get(
                   "alexnet", []))
    md = render_report(records, title="t")
    assert "Failures & retries (1 quarantined" in md
    assert "`RuntimeError` | 1" in md
    # pooled_records keeps last-wins semantics across failure/success
    later_ok = dict(records[0], cell_key=KU115_KEY)
    assert is_ok(pooled_records([[_quarantined(), later_ok]])[0])
    assert not is_ok(pooled_records([[later_ok, _quarantined()]])[0])


def test_report_tail_skips_quarantined_from_frontier(tmp_path,
                                                     monkeypatch):
    plan = FaultPlan({KU115_KEY: Fault("raise-permanent")})
    monkeypatch.setenv(ENV_VAR, str(plan.save(tmp_path / "p.json")))
    report = run_campaign(CELLS2, str(tmp_path / "s.jsonl"), **FAST)
    fi = report.frontier_index()
    assert all(fi.payload(k)["cell_key"] != KU115_KEY
               for k in fi.front_keys())
    assert all(r["cell_key"] != KU115_KEY for r in report.ranked())


# ---------------------------------------------------------------------------
# pool campaigns: crash recovery, timeouts
# ---------------------------------------------------------------------------


def test_pool_worker_crash_rebuilds_and_loses_no_cell(tmp_path,
                                                      monkeypatch):
    plan = FaultPlan({KU115_KEY: Fault("crash-process", (1,))})
    monkeypatch.setenv(ENV_VAR, str(plan.save(tmp_path / "p.json")))
    report = run_campaign(CELLS2, str(tmp_path / "s.jsonl"), workers=2,
                          policy=RetryPolicy(backoff_s=0.001), **FAST)
    assert not report.partial and exit_code(report) == 0
    assert report.pool_rebuilds >= 1
    assert len(report.records) == len(CELLS2)       # nothing lost
    assert all(is_ok(r) for r in report.records)
    crashed = {r["cell_key"]: r for r in report.records}[KU115_KEY]
    outcomes = [a["outcome"]
                for a in crashed["resilience"]["attempt_log"]]
    assert outcomes[0] == "crash" and outcomes[-1] == "ok"


def test_pool_cell_timeout_quarantines_hung_cell(tmp_path, monkeypatch):
    plan = FaultPlan({KU115_KEY: Fault("hang-for", (), hang_s=60.0)})
    monkeypatch.setenv(ENV_VAR, str(plan.save(tmp_path / "p.json")))
    report = run_campaign(
        CELLS2, str(tmp_path / "s.jsonl"), workers=2,
        policy=RetryPolicy(max_attempts=1, cell_timeout_s=1.5), **FAST)
    assert report.partial and exit_code(report) == 3
    failed = {r["cell_key"]: r for r in report.failures()}
    assert failed[KU115_KEY]["error_type"] == "CellTimeout"
    ok = [r for r in report.records if is_ok(r)]
    assert {r["cell_key"] for r in ok} \
        == {c.key for c in CELLS2 if c.key != KU115_KEY}


def test_pool_deadline_starts_when_the_cell_runs(tmp_path, monkeypatch):
    """A cell's timeout runs from when its worker starts it, not from
    submit: eight 0.5-s cells on two workers all finish under a 1.5-s
    timeout, though the last pair starts 1.5 s or more after the first
    and the workers spawn meanwhile."""
    cells = expand_cells(["alexnet"], [(224, 224)],
                         ["ku115", "zcu102", "vu9p", "zc706"], [16, 8], [1])
    plan = FaultPlan({c.key: Fault("hang-for", (), hang_s=0.5)
                      for c in cells})
    monkeypatch.setenv(ENV_VAR, str(plan.save(tmp_path / "p.json")))
    report = run_campaign(
        cells, str(tmp_path / "s.jsonl"), workers=2,
        policy=RetryPolicy(max_attempts=1, cell_timeout_s=1.5), **FAST)
    assert not report.partial and report.pool_rebuilds == 0
    assert len(report.records) == len(cells)
    assert all(is_ok(r) for r in report.records)


# ---------------------------------------------------------------------------
# signal-driven shutdown (subprocess: signal handlers are main-thread)
# ---------------------------------------------------------------------------


def test_sigint_flushes_store_and_exits_3(tmp_path):
    store = tmp_path / "s.jsonl"
    plan = FaultPlan({KU115_KEY: Fault("hang-for", (), hang_s=120.0)})
    env = dict(os.environ, REPRO_FAULTS=str(plan.save(tmp_path / "p.json")),
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    cmd = [sys.executable, "-m", "repro.dse.campaign",
           "--nets", "alexnet", "--fpgas", "ku115,zcu102",
           "--precisions", "16,8", "--batch-caps", "1",
           "--population", "4", "--iterations", "2",
           "--workers", "2", "--store", str(store)]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 60
        # wait for a non-hung cell to land, proving work-before-signal
        while time.time() < deadline:
            if store.exists() and store.stat().st_size > 0:
                break
            time.sleep(0.1)
        else:
            pytest.fail("no record appeared before the signal")
        time.sleep(0.5)
        proc.send_signal(signal.SIGINT)
        out, _ = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 3, out
    assert "partial campaign" in out and "exit code 3" in out
    assert "resume: re-run the same command" in out
    # the flushed store resumes cleanly: every stored record is intact
    recs = list(open_store(str(store)).iter_records())
    assert recs and all(is_ok(r) for r in recs)
    assert all(r["cell_key"] != KU115_KEY for r in recs)   # hung cell
