"""SolveReport: a structured, machine-readable record of one solve.

One JSON-round-trippable object per solve: problem shape, the full
`ProblemOption` configuration, backend/device topology, per-phase wall
clock (utils/timing.PhaseTimer), device memory stats when the backend
exposes them (utils/meminfo), the final result scalars, and the
materialized on-device convergence trace (trace.SolveTrace).

The sink is opt-in JSONL: `MEGBA_TELEMETRY=<path>` (or the `telemetry`
knob on `ProblemOption`) appends one line per `flat_solve` call — for a
checkpointed solve that is one line per CHUNK, each carrying that
chunk's own iterations/costs/trace (preemption forensics; the stitched
whole-solve trace lives on the returned `LMResult.trace`).  `python -m
megba_tpu.observability.summarize <path>` renders them.  When telemetry
is off this module is never imported (the package `__init__` loads it
lazily and solve.py gates the import on the knob) — the hot path pays
nothing.
"""

from __future__ import annotations

import dataclasses
import enum
import json
import os
import time
from typing import Any, Dict, Optional

import numpy as np

# Schema v2 (PR 16): adds request-scoped identity — `trace_id`/`span_id`
# (the active span context when MEGBA_TRACE is armed) and `worker` (the
# MEGBA_FEDERATION_WORKER tag, promoted from the fleet block to a
# first-class field so multi-worker JSONL aggregation doesn't need to
# dig).  All three are optional and `from_json` filters to known fields,
# so v1 lines load unchanged (MIGRATION.md notes the bump).
SCHEMA = "megba_tpu.solve_report/v2"


def _status_name(code) -> str:
    from megba_tpu.common import status_name

    return status_name(code)


def config_to_dict(option) -> Dict[str, Any]:
    """Serialize an option dataclass tree to plain JSON types.

    Enums become their names, dtypes their numpy names, nested option
    dataclasses nested dicts — generic over the option structs so a new
    field can never silently vanish from reports.
    """
    def conv(v):
        if dataclasses.is_dataclass(v) and not isinstance(v, type):
            return {f.name: conv(getattr(v, f.name))
                    for f in dataclasses.fields(v)}
        if isinstance(v, enum.Enum):
            return v.name
        if isinstance(v, (np.integer, np.floating, np.bool_)):
            return v.item()
        if isinstance(v, (np.dtype, type)):
            return np.dtype(v).name
        return v

    return conv(option)


def backend_topology() -> Dict[str, Any]:
    """Backend + device/process topology of THIS run."""
    import jax

    devices = jax.devices()
    kinds = sorted({d.device_kind for d in devices})
    return {
        "backend": jax.default_backend(),
        "device_count": len(devices),
        "local_device_count": len(jax.local_devices()),
        "device_kinds": kinds,
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
    }


@dataclasses.dataclass
class SolveReport:
    """One solve's telemetry record; `to_json`/`from_json` round-trip."""

    problem: Dict[str, Any]  # num_cameras / num_points / num_edges / ...
    config: Dict[str, Any]  # serialized ProblemOption
    backend: Dict[str, Any]  # platform + device/process topology
    phases: Dict[str, Any]  # PhaseTimer.as_dict(): name -> {total_s, calls}
    result: Dict[str, Any]  # final scalars (costs, iterations, ...)
    trace: Optional[Dict[str, list]] = None  # trace.trace_to_dict output
    memory: Optional[Dict[str, Any]] = None  # utils.meminfo.device_memory_stats
    # Optional serving-layer context (serving/batcher.py): bucket/lane
    # placement, batch latency and a FleetStats snapshot for reports
    # emitted by `solve_many` / `FleetQueue` — the fields the
    # `summarize --aggregate` fleet view keys on.
    fleet: Optional[Dict[str, Any]] = None
    # Optional elastic-distribution context (robustness/elastic.py): a
    # snapshot of one rank's ElasticMonitor ledger — workers lost,
    # collective timeouts, reshards, resumes, time-to-detection samples,
    # keyed by a `monitor` id so the aggregate view can take the LAST
    # snapshot per monitor and sum across monitors without double
    # counting (chunked solves emit one snapshot per chunk).
    elastic: Optional[Dict[str, Any]] = None
    # Optional federation context (serving/federation.py): a
    # FederationStats snapshot — per-worker problem counts, steals,
    # reroutes, worker-lost events and cold-start (artifact-load vs
    # compile) timings — keyed by a `router` id so the aggregate view
    # can take the LAST snapshot per router without double counting.
    # Emitted once per router lifetime by `append_federation_report`.
    federation: Optional[Dict[str, Any]] = None
    # Optional pre-flight triage context (robustness/triage.py): the
    # HealthReport dict of this solve's problem — findings by kind,
    # component count, the action taken and (after REPAIR) the repair
    # counters the `summarize --aggregate` triage view sums.  REJECTED
    # problems never emit a report (zero dispatch): their count rides
    # the fleet stats embedded in later reports, like sheds.
    health: Optional[Dict[str, Any]] = None
    # Request-scoped identity (schema v2, observability plane): the
    # active trace/span context this solve ran under (None when tracing
    # is off) and the federation worker id that produced the line (None
    # outside a worker process).  Lets `summarize --fleet` stitch one
    # fleet solve's reports across N worker JSONL files and lets the
    # trace-event export cross-reference report lines by span id.
    trace_id: Optional[str] = None
    span_id: Optional[str] = None
    worker: Optional[str] = None
    # Optional tile-plan metrics (solve.flat_solve): the streaming
    # reuse_factor / tile-occupancy statistics of the planned edge
    # stream — and, under SolverOption.fused_kernels, the per-direction
    # fused bucket-plan summaries — so a fused-kernel win (or the lack
    # of one on a reuse-poor scene) is attributable per solve.  None on
    # the non-tiled lowerings and on pre-existing report lines.
    tiles: Optional[Dict[str, Any]] = None
    schema: str = SCHEMA
    created_unix: float = 0.0

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, line: str) -> "SolveReport":
        d = json.loads(line)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


def _decode_fallback_totals(trace, iterations: int) -> Optional[Dict[str, Any]]:
    """Sum the enum-coded per-iteration precond_fallback codes into
    per-level totals; None without a trace.

    'block' = total SCHUR_DIAG camera blocks fallen back to Hpp;
    'coarse' = iterations where ANY hierarchy coarse level degraded
    (for two-level traces this is exactly the historical 0/1-per-iter
    sum); 'coarse_levels' (present only when a coarse degrade
    occurred) = per-hierarchy-level iteration counts, index l-1 =
    coarse level l — the multilevel bit-field, unpacked."""
    if trace is None or getattr(trace, "precond_fallback", None) is None:
        return None
    from megba_tpu.solver.precond import (
        decode_precond_fallback,
        decode_precond_fallback_levels,
    )

    block = coarse = 0
    per_level: list = []
    for code in np.asarray(trace.precond_fallback)[:iterations]:
        code = int(code)
        block += decode_precond_fallback(code)["block"]
        levels = decode_precond_fallback_levels(code)
        if any(levels):
            coarse += 1
        for i, flag in enumerate(levels):
            while len(per_level) <= i:
                per_level.append(0)
            per_level[i] += int(flag)
    out: Dict[str, Any] = {"block": int(block), "coarse": int(coarse)}
    if any(per_level):
        out["coarse_levels"] = per_level
    return out


def build_report(option, result, phases: Dict[str, Any],
                 problem: Dict[str, Any],
                 fleet: Optional[Dict[str, Any]] = None,
                 elastic: Optional[Dict[str, Any]] = None,
                 health: Optional[Dict[str, Any]] = None,
                 tiles: Optional[Dict[str, Any]] = None) -> SolveReport:
    """Assemble a SolveReport from a finished solve.

    `result` is an LMResult (trace included when the solve populated
    one); this call materializes the trace and result scalars, so the
    caller must be prepared for the implied device sync — telemetry-off
    paths never get here.
    """
    from megba_tpu.observability.trace import trace_to_dict
    from megba_tpu.utils.meminfo import device_memory_stats

    iterations = int(result.iterations)
    trace = getattr(result, "trace", None)
    result_block = {
            "initial_cost": float(result.initial_cost),
            "final_cost": float(result.cost),
            "iterations": iterations,
            "accepted": int(result.accepted),
            "pcg_iterations": int(result.pcg_iterations),
            "region": float(result.region),
            "stopped": bool(result.stopped),
            # Termination semantics (robustness layer): the status CODE
            # and its name, plus the contained-recovery count — the
            # fields an alerting pipeline keys on.
            "status": (None if getattr(result, "status", None) is None
                       else int(result.status)),
            "status_name": (
                None if getattr(result, "status", None) is None
                else _status_name(result.status)),
            "recoveries": (
                None if getattr(result, "recoveries", None) is None
                else int(result.recoveries)),
            # Per-LEVEL preconditioner fallback totals decoded from the
            # trace's enum-coded per-iteration counts (solver/precond):
            # "block" = SCHUR_DIAG camera blocks fallen back to Hpp,
            # "coarse" = two-level coarse factors degraded to
            # block-Jacobi.  None without a trace.
            "precond_fallback": _decode_fallback_totals(trace, iterations),
    }
    span_ctx = None
    from megba_tpu import observability as _obs

    recorder = _obs.span_recorder()
    if recorder is not None:
        span_ctx = recorder.context()
    return SolveReport(
        problem=problem,
        config=config_to_dict(option),
        backend=backend_topology(),
        phases=phases,
        result=result_block,
        trace=None if trace is None else trace_to_dict(trace, iterations),
        memory=device_memory_stats(),
        fleet=fleet,
        elastic=elastic,
        health=health,
        tiles=tiles,
        trace_id=None if span_ctx is None else span_ctx["trace_id"],
        span_id=None if span_ctx is None else span_ctx["span_id"],
        worker=os.environ.get("MEGBA_FEDERATION_WORKER") or None,
        created_unix=time.time(),
    )


def append_report(report: SolveReport, path: str) -> None:
    """Append one report as a JSONL line (creates parent dirs)."""
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    with open(path, "a") as fh:
        fh.write(report.to_json() + "\n")
