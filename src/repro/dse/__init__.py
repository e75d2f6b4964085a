"""repro.dse — backend-agnostic multi-objective DSE campaigns.

:mod:`repro.core.explorer` runs DNNExplorer's 3-step flow (Fig. 4) for ONE
(DNN, FPGA) pair and one scalar objective. This package lifts that to the
campaign scale the paper's evaluation actually operates at ("different
combinations of DNN workloads and targeted FPGAs", Tables 3/4, Figs. 9-11)
— and widens "targeted FPGAs" to targeted *device families*:

1. *Backends* — :mod:`repro.dse.backends` gives each device family a
   campaign contract: an objective schema, cell expansion over that
   family's axes, per-cell evaluation, and a resume-match search config.
   The ``fpga`` backend (default) sweeps (network x input size x FPGA x
   precision x batch cap) with one PSO search per cell; the ``tpu``
   backend sweeps (arch x shape x chip count x remat x microbatches)
   through the analytic planner in :mod:`repro.core.tpu_planner`; the
   ``cuda`` backend adds a GPU-part axis (A100-40G/A100-80G/H100) over
   the GPU roofline in :mod:`repro.core.gpu_model` /
   :mod:`repro.core.gpu_planner`.
2. *Campaign running* — :mod:`repro.dse.campaign` fans a backend's cells
   out over a process pool with deterministic per-cell seeds, collecting
   records into a resumable JSONL store as they finish.
3. *Multi-objective evaluation* — :mod:`repro.dse.objectives` defines the
   schema machinery (canonical maximization form, weighted
   scalarization); each backend declares its own vector (FPGA:
   throughput img/s, GOP/s, latency, DSP efficiency, BRAM; TPU: step
   time, MFU, HBM per chip, chips used; CUDA: the TPU vector plus board
   watts) — plus the NORMALIZED cross-backend schema (delivered TFLOP/s,
   per watt, per dollar-proxy, per peak TFLOP) every backend can emit
   via ``Backend.normalized(record)``, so one frontier compares device
   families.
4. *Frontier extraction* — :mod:`repro.dse.pareto` non-dominated-sorts
   the campaign's designs into Pareto fronts and, NSGA-II-style, orders
   them by crowding distance so a truncated frontier is a SPREAD across
   the trade-off surface (extremes kept, clumps thinned);
   ``CampaignReport.frontier(k=N)`` returns the N most-diverse designs.
5. *Persistence* — :mod:`repro.dse.store` appends every finished cell to
   a JSON-lines store keyed on the cell key; re-running a campaign reuses
   stored cells, which makes killed campaigns resumable and repeat cells
   free across runs. A record's ``search`` holds its whole resume key; a
   cell stored under another key re-runs.
6. *Reporting* — :mod:`repro.dse.report` renders any store (plus optional
   ``benchmarks/run.py --json`` output) into a Markdown campaign report:
   frontier tables, per-workload winners, objective trade-off summaries,
   and — for stores mixing device families — a cross-backend normalized
   frontier. ``--compare A B [C ...]`` renders the trajectory between
   stores: per-workload winner deltas, best-objective trajectories, and
   a pooled cross-backend frontier.
7. *Telemetry* — :mod:`repro.obs` threads structured spans, counters,
   and gauges through the campaign runner (``--trace``): per-cell
   queue-wait/eval/append spans from every pool worker land in
   ``<store>.events.jsonl`` (merged deterministically from per-worker
   sidecars), and the parent's spans land in a running JAX profiler
   trace; every record carries a ``trace`` field with the search's
   convergence history and stop reason. ``python -m repro.dse.obs``
   summarizes, validates, and exports; the report gains a
   campaign-health section.

Quickstart (see also ``examples/dse_campaign.py`` and ``README.md``)::

    # FPGA campaign (the paper's flow; default backend):
    python -m repro.dse.campaign --nets vgg16 --fpgas ku115,zcu102 \\
        --precisions 16,8 --store results/dse.jsonl

    # TPU campaign (beyond-paper retarget of the same engine):
    python -m repro.dse.campaign --backend tpu --archs starcoder2-3b,xlstm-350m \\
        --shapes train_4k,decode_32k --chips 8,16,32 --store results/dse_tpu.jsonl

    # CUDA campaign (GPU roofline; the GPU part is a campaign axis):
    python -m repro.dse.campaign --backend cuda --archs starcoder2-3b \\
        --shapes train_4k,decode_32k --gpus 8,16,32 \\
        --gpu-types a100-80g,h100 --store results/dse_cuda.jsonl

    # Markdown report (frontier tables, per-workload winners, trade-offs;
    # mixed stores also get a cross-backend normalized frontier):
    python -m repro.dse.report results/dse.jsonl --out docs/reports/fpga.md

    # Compare stores: winner deltas + objective trajectories:
    python -m repro.dse.report --compare results/dse_tpu.jsonl \\
        results/dse_cuda.jsonl --out docs/reports/tpu_vs_cuda.md
"""
from .objectives import (NORMALIZED_DEFAULT_WEIGHTS, NORMALIZED_OBJECTIVES,
                         OBJECTIVES, ObjectiveSpec, Objectives,
                         canonical_vector, normalized_throughput,
                         scalarize_values, scalarized_objective)
from .frontier import FrontierIndex
from .pareto import (crowding_distance, diverse_front, dominance_split,
                     dominates, non_dominated, nondominated_sort,
                     pareto_front, select_diverse)

# Campaign/backend/report/store exports resolve lazily (PEP 562) so
# `python -m repro.dse.campaign` / `python -m repro.dse.report` /
# `python -m repro.dse.store` don't import their module twice (runpy's
# found-in-sys.modules warning).
_CAMPAIGN_EXPORTS = ("CampaignCell", "CampaignReport", "cell_seed",
                     "expand_cells", "prescreen_cells_jax", "run_campaign",
                     "run_cell")
_BACKEND_EXPORTS = ("BACKENDS", "Backend", "CUDABackend", "CUDACell",
                    "FPGABackend", "GPU_OBJECTIVES", "TPUBackend",
                    "TPUCell", "TPU_OBJECTIVES", "get_backend",
                    "workload_families")
_REPORT_EXPORTS = ("fixture_events", "fixture_records", "health_section",
                   "render_compare", "render_placement", "render_report")
_OBS_EXPORTS = ("events_for_store", "example_health_md")
_STORE_EXPORTS = ("CampaignStore", "ResultStore", "is_ok", "open_store",
                  "rav_hash", "record_status")
_RESILIENCE_EXPORTS = ("CellOutcome", "CellTimeout", "CorruptRecord",
                       "RetryPolicy", "WorkerCrash", "execute_cell",
                       "interrupt_scope", "quarantine_record",
                       "run_resilient_pool")
_PLACEMENT_EXPORTS = ("Assignment", "BudgetInfeasibleError", "Candidate",
                      "CoverageError", "PlacementError", "PlacementResult",
                      "candidates_by_workload", "ensure_coverage",
                      "marginal_upgrades", "parse_workloads", "place",
                      "pooled_records", "prune_candidates")

__all__ = [
    *_CAMPAIGN_EXPORTS, *_BACKEND_EXPORTS, *_REPORT_EXPORTS,
    *_PLACEMENT_EXPORTS, *_OBS_EXPORTS, *_RESILIENCE_EXPORTS,
    "is_ok", "record_status",
    "NORMALIZED_DEFAULT_WEIGHTS", "NORMALIZED_OBJECTIVES",
    "OBJECTIVES", "ObjectiveSpec", "Objectives", "canonical_vector",
    "normalized_throughput", "scalarize_values", "scalarized_objective",
    "crowding_distance", "diverse_front", "dominance_split", "dominates",
    "non_dominated", "nondominated_sort", "pareto_front", "select_diverse",
    "CampaignStore", "FrontierIndex", "ResultStore", "open_store",
    "rav_hash",
]


def __getattr__(name: str):
    if name in _CAMPAIGN_EXPORTS:
        from . import campaign
        return getattr(campaign, name)
    if name in _BACKEND_EXPORTS:
        from . import backends
        return getattr(backends, name)
    if name in _REPORT_EXPORTS:
        from . import report
        return getattr(report, name)
    if name in _PLACEMENT_EXPORTS:
        from . import placement
        return getattr(placement, name)
    if name in _OBS_EXPORTS:
        from . import obs
        return getattr(obs, name)
    if name in _STORE_EXPORTS:
        from . import store
        return getattr(store, name)
    if name in _RESILIENCE_EXPORTS:
        from . import resilience
        return getattr(resilience, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
