"""Benchmark harness — prints ONE JSON line for the driver.

Measures LM iterations/second on a synthetic problem shaped like one of
the five BASELINE.md configurations (MEGBA_BENCH_CONFIG = ladybug /
trafalgar / venice / final / final_mixed; default venice — 1778 cameras,
993,923 points, ~5.0M observations, analytical Jacobian, implicit Schur
PCG, float32) on whatever accelerator JAX provides (the real TPU chip
under the driver).

Problem shapes match the real BAL datasets: camera and point counts are
exact; the observation count is matched via a fractional obs-per-point
(the sandbox has no network egress, so the geometry is synthetic — see
megba_tpu/io/synthetic.py).

The reference repo publishes no absolute numbers (BASELINE.md), so
`vs_baseline` is computed against a DERIVED reference rate: a memory-
bandwidth + launch-latency roofline of the reference's CUDA pipeline
(explicit CSR SpMV or implicit edge-scatter, per config) running the
same problem shape and the same PCG iteration count on one A100-40GB.
The full derivation, constants, and their sources are written down in
BASELINE.md §"Derived baseline".
"""

from __future__ import annotations

import json
import time

import numpy as np

import os

# The five BASELINE.md configs, selectable via MEGBA_BENCH_CONFIG
# (default: venice — the headline metric).  cameras/points are the real
# BAL dataset counts; obs_per_point is chosen so the synthetic edge
# count matches the dataset's observation count (BASELINE.md table).
from typing import NamedTuple


class BenchConfig(NamedTuple):
    cameras: int
    points: int
    obs_per_point: float
    dtype: str
    jacobian: str
    compute: str
    mixed: bool = False
    force_cpu: bool = False
    # Reference-side model inputs for the derived baseline (BASELINE.md):
    # the dtype the reference example for this config runs in, and whether
    # its solver path is implicit (matrix-free) or explicit (CSR SpMV).
    ref_dtype_bytes: int = 8
    ref_implicit: bool = False


CONFIGS = {
    # BAL Ladybug problem-49-7776 (31,843 obs): BAL_Double, CPU, world 1.
    "ladybug": BenchConfig(49, 7776, 31_843 / 7776, "float64", "AUTODIFF",
                           "EXPLICIT", force_cpu=True),
    # BAL Trafalgar problem-257-65132 (225,911 obs): BAL_Float autodiff.
    "trafalgar": BenchConfig(257, 65_132, 225_911 / 65_132, "float32",
                             "AUTODIFF", "EXPLICIT", ref_dtype_bytes=4),
    # BAL Venice problem-1778-993923 (~5.0M obs): BAL_Double_analytical.
    "venice": BenchConfig(1778, 993_923, 5_001_946 / 993_923, "float32",
                          "ANALYTICAL", "IMPLICIT"),
    # BAL Final problem-13682-4456117 (~29.0M obs): analytical implicit.
    "final": BenchConfig(13_682, 4_456_117, 28_987_644 / 4_456_117, "float32",
                         "ANALYTICAL", "IMPLICIT", ref_implicit=True),
    # Final, mixed precision: fp32 residuals + bf16 PCG.
    "final_mixed": BenchConfig(13_682, 4_456_117, 28_987_644 / 4_456_117,
                               "float32", "ANALYTICAL", "IMPLICIT", mixed=True,
                               ref_implicit=True),
}


def derived_baseline_lm_iters_per_sec(
    n_edge: int,
    n_cam: int,
    n_pt: int,
    pcg_iters: float,
    ref_dtype_bytes: int,
    implicit: bool,
) -> float:
    """Reference (MegBA/CUDA) LM-iteration rate modelled on one A100-40GB.

    Roofline = HBM traffic / (efficiency x bandwidth) + kernel-launch and
    host-sync latency.  Traffic counts follow the reference's own data
    structures (SURVEY.md §3.3/§3.5); constants documented in BASELINE.md.
    """
    B = ref_dtype_bytes
    nnz = 27 * n_edge  # scalar nnz of Hpl: 9x3 block per edge
    # Two forward passes per LM iter (reference re-runs forward for rho and
    # rebuilds on accept): read 12 param scalars, write 24 J + 2 e per edge.
    fwd_bytes = 2 * (12 + 24 + 2) * B * n_edge
    # Hessian build: read J + e, write Hpl/Hlp CSR + block diags + g.
    build_bytes = (26 + 2 * 27) * B * n_edge + (81 * n_cam + 9 * n_pt) * B
    if implicit:
        # Per PCG iter: EMulx + ETMulx re-read Jc(18)+Jp(6) per edge + idx.
        per_pcg = 2 * 24 * B * n_edge + 2 * 8 * n_edge
    else:
        # Per PCG iter: two CSR SpMVs read vals + int32 colInd.
        per_pcg = 2 * nnz * (B + 4)
    # Both paths: Hpp gemv, Hll^-1 apply, ~4 full camera+point vector sweeps.
    per_pcg += (81 * n_cam + 9 * n_pt + 4 * (9 * n_cam + 3 * n_pt)) * B
    total_bytes = fwd_bytes + build_bytes + pcg_iters * per_pcg

    A100_BW = 1.555e12  # A100-40GB peak HBM bandwidth, B/s
    EFF = 0.60          # generous streaming efficiency for cuSPARSE/cuBLAS
    bw_time = total_bytes / (EFF * A100_BW)

    # Latency: the reference's op-per-kernel autodiff (~40 launches/forward,
    # SURVEY.md §3.4), ~10 kernels + 2 host-blocking dot reductions per PCG
    # iter (§3.5), ~6 host syncs per LM iter (§3.2).
    LAUNCH, SYNC = 5e-6, 10e-6
    lat_time = (2 * 40 + 10) * LAUNCH + pcg_iters * (10 * LAUNCH + 2 * SYNC) + 6 * SYNC
    return 1.0 / (bw_time + lat_time)

CONFIG = os.environ.get("MEGBA_BENCH_CONFIG", "venice")
if CONFIG not in CONFIGS:
    raise SystemExit(
        f"unknown MEGBA_BENCH_CONFIG {CONFIG!r}; choose from {sorted(CONFIGS)}")

# MEGBA_BENCH_SCALE in (0, 1] shrinks the problem for smoke tests.
_SCALE = float(os.environ.get("MEGBA_BENCH_SCALE", "1.0"))

# MEGBA_BENCH_MESH2D=<ExC> (e.g. "2x2"): 2-D mesh head-to-head vs the
# 1-D edge sharding at the same world size (mesh2d_head_to_head).  The
# backend needs E*C devices; on the CPU lane that means forcing virtual
# host devices BEFORE backend init, so the knob is resolved here.
_MESH2D_SPEC = os.environ.get("MEGBA_BENCH_MESH2D", "")


def _parse_mesh2d(spec: str):
    try:
        e, c = spec.lower().replace(" ", "").split("x")
        e, c = int(e), int(c)
    except ValueError:
        raise SystemExit(
            f"MEGBA_BENCH_MESH2D must look like '2x2', got {spec!r}")
    if e < 1 or c < 1:
        raise SystemExit(
            f"MEGBA_BENCH_MESH2D needs positive factors, got {spec!r}")
    return e, c


if _MESH2D_SPEC:
    _E2D, _C2D = _parse_mesh2d(_MESH2D_SPEC)
    # Raise-to-floor, not append-if-absent: a pre-set LOWER count
    # (persisted dev-shell/CI XLA_FLAGS) would otherwise silently skip
    # the whole head-to-head.  Importing the audit module is safe here:
    # it only touches XLA_FLAGS, and the backend has not initialised.
    from megba_tpu.analysis.audit import ensure_host_device_floor

    os.environ["XLA_FLAGS"] = ensure_host_device_floor(
        os.environ.get("XLA_FLAGS", ""), _E2D * _C2D)

# MEGBA_BENCH_BF16=1: bf16 MXU pipeline vs f32 head-to-head
# (bf16_head_to_head) writing BENCH_bf16.json.  The structural half of
# the evidence live-audits the ba_bf16_w2_f32 canonical program (world
# 2), so the CPU lane needs >= 2 virtual devices before backend init.
_BF16_BENCH = os.environ.get("MEGBA_BENCH_BF16") == "1"
if _BF16_BENCH:
    from megba_tpu.analysis.audit import ensure_host_device_floor

    os.environ["XLA_FLAGS"] = ensure_host_device_floor(
        os.environ.get("XLA_FLAGS", ""), 2)
# MEGBA_BENCH_OBS=1: observability-plane overhead head-to-head
# (obs_head_to_head) writing BENCH_obs.json.  Entirely host-side — the
# plane never touches the jitted programs — so no device floor needed.
_OBS_BENCH = os.environ.get("MEGBA_BENCH_OBS") == "1"
# MEGBA_BENCH_FUSED=1: fused Pallas edge-pipeline kernels vs the tiled
# XLA lowering (fused_head_to_head) writing BENCH_fused.json.
# Single-device tiled path — no device floor needed.
_FUSED_BENCH = os.environ.get("MEGBA_BENCH_FUSED") == "1"
_C = CONFIGS[CONFIG]
NUM_CAMERAS = max(8, int(_C.cameras * _SCALE))
NUM_POINTS = max(64, int(_C.points * _SCALE))
OBS_PER_POINT = _C.obs_per_point
LM_ITERS = 8
PCG_ITERS = 30


def _device_tag() -> str:
    """Platform, device kind and count, as JAX reports them."""
    import jax

    d = jax.devices()
    return f"{d[0].platform} {d[0].device_kind} x{len(d)}"


def _status_name(res):
    if getattr(res, "status", None) is None:
        return None
    from megba_tpu.common import status_name

    return status_name(res.status)


def fleet_head_to_head(n_problems: int, dtype, timer) -> dict:
    """Serial flat_solve loop vs batched solve_many over one fleet.

    Both sides solve the SAME `io/synthetic.make_fleet` problems to the
    same convergence settings, and both are fully warmed (compiles +
    host plan caches) before timing, so the comparison is steady-state
    dispatch throughput — the regime a long-lived service runs in.  The
    serial side pays one `flat_solve` call per problem (per-call host
    prep + dispatch); the batched side pays one padded dispatch per
    shape bucket.

    `max_cost_rel_gap` compares the batched lanes against the serial
    loop at each problem's NATURAL shape.  Runs at the surrounding
    bench dtype: under the default f32 lane (x64 off) camera/point
    padding reorders the compensated sums, so un-converged trajectories
    drift ~1e-2 relative — a sanity band, not a parity proof.  The
    bitwise-padding / rtol-1e-6 parity contract is pinned where it is
    provable, in tests/test_serving.py under x64.
    """
    import jax

    from megba_tpu.common import AlgoOption, ProblemOption, SolverOption
    from megba_tpu.io.synthetic import make_fleet
    from megba_tpu.ops.residuals import make_residual_jacobian_fn
    from megba_tpu.serving import FleetProblem, FleetStats, solve_many
    from megba_tpu.solve import flat_solve

    opt = ProblemOption(
        dtype=dtype,
        algo_option=AlgoOption(max_iter=8),
        solver_option=SolverOption(max_iter=12, tol=1e-8))
    fleet = make_fleet(n_problems, size_range=(16, 64), seed=0, dtype=dtype)
    probs = [FleetProblem.from_synthetic(s, name=f"fleet{i}")
             for i, s in enumerate(fleet)]
    f = make_residual_jacobian_fn(mode=opt.jacobian_mode)

    def serial_pass():
        out = [flat_solve(f, p.cameras, p.points, p.obs, p.cam_idx,
                          p.pt_idx, opt, use_tiled=False) for p in probs]
        jax.block_until_ready([r.cost for r in out])
        return out

    with timer.phase("fleet_warm_serial"):
        serial_pass()
    t0 = time.perf_counter()
    with timer.phase("fleet_serial"):
        serial = serial_pass()
    serial_s = time.perf_counter() - t0

    with timer.phase("fleet_warm_batched"):
        solve_many(probs, opt)
    stats = FleetStats()
    t0 = time.perf_counter()
    with timer.phase("fleet_batched"):
        batched = solve_many(probs, opt, stats=stats)
    batched_s = time.perf_counter() - t0

    cost_gap = max(
        abs(float(b.cost) - float(s.cost)) / max(abs(float(s.cost)), 1e-30)
        for b, s in zip(batched, serial))
    d = stats.as_dict()
    return {
        "problems": n_problems,
        "problems_per_sec_serial": round(n_problems / serial_s, 2),
        "problems_per_sec_batched": round(n_problems / batched_s, 2),
        "speedup": round(serial_s / batched_s, 3),
        "serial_s": round(serial_s, 4),
        "batched_s": round(batched_s, 4),
        "buckets": len(d["per_bucket"]),
        "padding_waste": round(d["padding_waste"], 4),
        "statuses": sorted({b.status_name for b in batched}),
        "serial_statuses": sorted(
            {_status_name(r) for r in serial}),
        "max_cost_rel_gap": cost_gap,
    }


def obs_head_to_head(n_problems: int, dtype, timer) -> dict:
    """Observability-plane overhead: solve_many with the plane OFF vs
    metrics+spans ON over the same warmed fleet.

    Both sides solve the SAME `make_fleet` problems (the
    fleet_head_to_head generator) after a shared warm pass; the jitted
    programs are byte-identical either way (the plane is host-side only,
    gated by `analysis/audit --check`), so any delta is pure host
    instrumentation cost — registry increments, span records, phase-hook
    dispatch.  Each side is timed best-of-3 (shared noisy container; see
    federation_head_to_head's rationale), and the acceptance band is
    <= 2% overhead (`within_band`), asserted by scripts/run_tests.sh on
    the venice lane.  Also written to BENCH_obs.json.
    """
    from megba_tpu.common import AlgoOption, ProblemOption, SolverOption
    from megba_tpu.io.synthetic import make_fleet
    from megba_tpu.observability import metrics as _metrics
    from megba_tpu.observability import spans as _spans
    from megba_tpu.serving import FleetProblem, solve_many

    opt = ProblemOption(
        dtype=dtype,
        algo_option=AlgoOption(max_iter=8),
        solver_option=SolverOption(max_iter=12, tol=1e-8))
    fleet = make_fleet(n_problems, size_range=(16, 64), seed=0, dtype=dtype)
    probs = [FleetProblem.from_synthetic(s, name=f"obs{i}")
             for i, s in enumerate(fleet)]

    def timed_pass() -> float:
        t0 = time.perf_counter()
        solve_many(probs, opt)
        return time.perf_counter() - t0

    # Neither side may inherit ambient plane state from the dev shell.
    saved = {k: os.environ.pop(k, None)
             for k in ("MEGBA_METRICS", "MEGBA_TRACE", "MEGBA_FLIGHT")}
    try:
        with timer.phase("obs_warm"):
            solve_many(probs, opt)
        # Arm metrics + spans (flight only fires on crash paths, so it
        # adds nothing to a clean run) against fresh default instances.
        _metrics.reset_default_registry()
        _spans.reset_default_recorder()
        # INTERLEAVED best-of-6 pairs: sequential blocks would charge
        # any monotone container drift (frequency scaling, a noisy
        # neighbour arriving) entirely to whichever side ran second —
        # on this shared box that drift alone exceeds the 2% band.
        # Alternating off/on reps puts both sides in the same weather,
        # and min() discards the on side's one-time lazy-import cost.
        off_s = on_s = float("inf")
        for _ in range(6):
            os.environ.pop("MEGBA_METRICS", None)
            os.environ.pop("MEGBA_TRACE", None)
            with timer.phase("obs_off"):
                off_s = min(off_s, timed_pass())
            os.environ["MEGBA_METRICS"] = "1"
            os.environ["MEGBA_TRACE"] = "1"
            with timer.phase("obs_on"):
                on_s = min(on_s, timed_pass())
        snap = _metrics.default_registry().snapshot()
        n_spans = len(_spans.default_recorder().drain())
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _metrics.reset_default_registry()
        _spans.reset_default_recorder()

    overhead_pct = 100.0 * (on_s - off_s) / off_s
    result = {
        "problems": n_problems,
        "off_s": round(off_s, 4),
        "on_s": round(on_s, 4),
        "overhead_pct": round(overhead_pct, 3),
        "band_pct": 2.0,
        "within_band": bool(overhead_pct <= 2.0),
        # Evidence the instrumented side actually instrumented: the
        # number of metric families populated and spans recorded.
        "metric_families": len(snap["metrics"]),
        "spans": n_spans,
    }
    artifact_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_obs.json")
    with open(artifact_path, "w") as fh:
        json.dump(result, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return result


def federation_head_to_head(n_workers: int, dtype, timer) -> dict:
    """Single-host FleetQueue vs an N-worker FleetRouter on one fleet,
    plus the replica cold-start split (artifact-load vs compile).

    THROUGHPUT: all sides solve the same `make_fleet` problems (the
    fleet_head_to_head generator, scaled up), fully warmed, each
    configuration timed best-of-2 (this sandbox is a shared 2-core
    container with a cgroup CPU quota — single measurements swing 2-3x
    under noisy neighbours, and two simultaneous pinned processes
    measure only ~1.15x ONE process's throughput, i.e. the quota caps
    aggregate compute below 2 honest cores).  Because of that cap the
    whole-machine comparison cannot show real scale-out here; the
    curve that CAN be certified on this lane is EQUAL-RESOURCE
    scaling: fed_1 and fed_N workers pinned to the SAME per-worker
    core slice (cores // N each), so the 1→N ratio measures what the
    router/stealing/IPC layer costs and gains per added host —
    `scaling_equal_resources` is the ROADMAP "~linear 1→N" observable,
    `scaling_vs_single_queue` is recorded for honesty with the machine
    context attached.

    COLD START: one fresh worker warmed from serialized artifacts vs
    one compiling from scratch, both measured config→fleet-solved over
    the same manifest buckets, full fleet submitted atomically
    (submit_many) so batch composition reproduces the exporter's and
    the artifact worker dispatches it with ZERO traces (worker-side
    retrace-sentinel certification, reported in the JSON).  Set
    MEGBA_BENCH_FEDERATION_COLD=0 to skip the (compile-heavy) cold
    half.  Results land in BENCH_federation.json next to the JSON line.
    """
    import tempfile

    from megba_tpu.serving.federation import local_worker_env

    # One process per chip: on a TPU this process holds the chip, so the
    # lane refuses (ChipOwnershipError) before any warm-up work.
    local_worker_env({})

    from megba_tpu.common import AlgoOption, ProblemOption, SolverOption
    from megba_tpu.io.synthetic import make_fleet
    from megba_tpu.ops.residuals import make_residual_jacobian_fn
    from megba_tpu.serving import (
        CompilePool,
        FleetProblem,
        FleetQueue,
        FleetRouter,
        FleetStats,
        solve_many,
    )

    n_problems = int(os.environ.get(
        "MEGBA_BENCH_FEDERATION_PROBLEMS", "32") or "32")
    opt = ProblemOption(
        dtype=dtype,
        algo_option=AlgoOption(max_iter=8),
        solver_option=SolverOption(max_iter=12, tol=1e-8))
    fleet = make_fleet(n_problems, size_range=(16, 64), seed=0, dtype=dtype)
    probs = [FleetProblem.from_synthetic(s, name=f"fed{i}")
             for i, s in enumerate(fleet)]
    engine = make_residual_jacobian_fn(mode=opt.jacobian_mode)

    root = tempfile.mkdtemp(prefix="megba_bench_fed_")
    manifest = os.path.join(root, "manifest.json")

    # -- exporter: deterministic bucket discovery through solve_many
    # (one batch per bucket — exactly what submit_many through the
    # router reproduces), then the portable-executable export ----------
    export_pool = CompilePool(stats=FleetStats(), artifacts=root)
    with timer.phase("federation_warm_export_pool"):
        solve_many(probs, opt, pool=export_pool)
    export_pool.save_manifest(manifest, option=opt)
    with timer.phase("federation_export"):
        exported = export_pool.export_artifacts(engine, opt)

    # -- single-host baseline: a warmed FleetQueue (own pool, jit path;
    # max_wait large so flush() drives one deterministic batch per
    # bucket) ----------------------------------------------------------
    qpool = CompilePool(stats=FleetStats())

    def queue_pass():
        stats = FleetStats()
        with FleetQueue(opt, max_batch=n_problems, max_wait_s=30.0,
                        pool=qpool, stats=stats) as q:
            futs = [q.submit(p) for p in probs]
            q.flush()
            out = [f.result(timeout=600) for f in futs]
        return out, stats

    with timer.phase("federation_warm_single"):
        queue_pass()
    single_s = float("inf")
    for _ in range(2):  # best-of-2: noisy-neighbour suppression
        t0 = time.perf_counter()
        with timer.phase("federation_single"):
            queue_pass()
        single_s = min(single_s, time.perf_counter() - t0)

    # -- cold start: artifact replica vs compile replica -----------------
    # Both replicas dispatch the FULL fleet, submitted atomically
    # (submit_many) with max_batch >= any bucket's population: batch
    # composition then reproduces the exporter's solve_many batches
    # exactly, so the artifact replica's first fleet rides the store
    # end to end — zero traces, the sentinel-certified contract.
    def replica_cold_start(artifacts):
        router = FleetRouter(opt, n_workers=1, artifacts=artifacts,
                             manifest=manifest, max_batch=n_problems)
        try:
            t0 = time.perf_counter()
            futs = router.submit_many(probs)
            router.flush()
            [f.result(timeout=600) for f in futs]
            first_solve_s = time.perf_counter() - t0
            d = router.stats.as_dict()
            cs = d["cold_start"]["w0"]
            fs = d["first_solve"]["w0"]
            return {
                "mode": cs["mode"],
                "warm_s": round(cs["warm_s"], 3),
                "first_solve_s": round(first_solve_s, 3),
                "cold_start_to_first_solve_s": round(
                    cs["warm_s"] + first_solve_s, 3),
                "buckets": cs["buckets"],
                "artifact_loads": cs["artifact_loads"],
                "artifact_compiles": cs["artifact_compiles"],
                "first_solve_traces": fs["traces"],
            }
        finally:
            router.close()

    cold = None
    if os.environ.get("MEGBA_BENCH_FEDERATION_COLD", "1") != "0":
        with timer.phase("federation_cold_artifact"):
            from_artifacts = replica_cold_start(root)
        with timer.phase("federation_cold_compile"):
            from_compile = replica_cold_start(None)
        cold = {
            "from_artifacts": from_artifacts,
            "from_compile": from_compile,
            "speedup": round(
                from_compile["cold_start_to_first_solve_s"]
                / max(from_artifacts["cold_start_to_first_solve_s"], 1e-9),
                2),
        }

    # -- federated throughput: equal-resource 1→N scaling ----------------
    # Every worker — in BOTH sweeps — is pinned to the same-size core
    # slice (cores // n_workers), so fed_1 is "one host" and fed_n is
    # "n hosts" of identical resources; the ratio is the scale-out
    # curve, isolated from this container's aggregate CPU quota.
    try:
        n_cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        n_cores = os.cpu_count() or 1
    per_worker_cores = max(1, n_cores // n_workers)

    def router_pass(workers):
        router = FleetRouter(opt, n_workers=workers, artifacts=root,
                             manifest=manifest, strict_manifest=True,
                             max_batch=n_problems,
                             pin_cpus=per_worker_cores)
        try:
            with timer.phase(f"federation_warm_x{workers}"):
                futs = router.submit_many(probs)
                router.flush()
                [f.result(timeout=600) for f in futs]
            wall = float("inf")
            out = None
            for _ in range(2):  # best-of-2
                t0 = time.perf_counter()
                with timer.phase(f"federation_x{workers}"):
                    futs = router.submit_many(probs)
                    router.flush()
                    res = [f.result(timeout=600) for f in futs]
                dt = time.perf_counter() - t0
                if dt < wall:
                    wall, out = dt, res
            return out, wall, router.stats.as_dict(), router.pinned
        finally:
            router.close()

    _, fed1_s, fed1_stats, fed1_pinned = router_pass(1)
    fed_out, fedn_s, fed_stats, fedn_pinned = router_pass(n_workers)

    result = {
        "workers": n_workers,
        "problems": n_problems,
        "exported_artifacts": exported,
        "machine": {
            "cores": n_cores,
            "per_worker_cores": per_worker_cores,
            # Equal-resource scaling is only CERTIFIED when pinning
            # actually applied in BOTH sweeps (n_workers > cores
            # leaves workers unpinned, with a warning — the ratio is
            # then asymmetric and must not be read as the curve).
            "pinned": bool(fed1_pinned and fedn_pinned),
            "note": ("shared container with a cgroup CPU quota: two "
                     "simultaneous pinned processes measure ~1.15x ONE "
                     "process (aggregate compute capped), so "
                     "scaling_vs_single_queue understates real "
                     "multi-host scale-out; scaling_equal_resources is "
                     "the certified curve"),
        },
        "problems_per_sec_single_queue": round(n_problems / single_s, 2),
        "problems_per_sec_federated_1": round(n_problems / fed1_s, 2),
        "problems_per_sec_federated_n": round(n_problems / fedn_s, 2),
        "scaling_vs_single_queue": round(single_s / fedn_s, 3),
        "scaling_equal_resources": round(fed1_s / fedn_s, 3),
        "single_queue_s": round(single_s, 3),
        "federated_1_s": round(fed1_s, 3),
        "federated_n_s": round(fedn_s, 3),
        "steals": fed_stats["steals"],
        "problems_by_worker": fed_stats["problems_by_worker"],
        "first_solve_traces": {
            w: fs.get("traces")
            for w, fs in fed_stats.get("first_solve", {}).items()},
        "statuses": sorted({r.status_name for r in fed_out}),
        "cold_start": cold,
    }
    artifact_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_federation.json")
    with open(artifact_path, "w") as fh:
        json.dump(result, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return result


def mesh2d_head_to_head(s, base_option, edge_shards, cam_blocks,
                        timer) -> dict:
    """2-D (edge_shards x cam_blocks) mesh vs 1-D edge sharding at the
    SAME world size on the same scene (MEGBA_BENCH_MESH2D=<ExC>).

    Records wall-clock (both sides warmed first), the static
    bytes-moved-per-CG-step census of each compiled program (ring
    model, analysis/hlo.collective_bytes_moved over the PCG-body
    collectives — the same model the budget gate pins), the tile
    geometry, and the co-observation plan's streaming reuse factor.
    Results land in BENCH_mesh2d.json.

    HONESTY TAG: this container's bench lane is CPU-only (~1.2 cores of
    aggregate quota), where virtual-device collectives are memcpys —
    wall-clock CANNOT show the ICI win here and usually shows 2-D
    slightly slower (the tile loop adds launches).  The structural
    bytes/census numbers are the transferable evidence; the wall-clock
    is recorded so the CPU-lane overhead is known, not hidden.
    """
    import dataclasses as _dc

    import jax

    from megba_tpu.analysis import hlo as hlo_mod
    from megba_tpu.analysis.program_audit import pcg_body_collective_summary
    from megba_tpu.ops.residuals import make_residual_jacobian_fn
    from megba_tpu.ops.segtiles import cached_camera_tile_plan
    from megba_tpu.solve import flat_solve

    world = edge_shards * cam_blocks
    if len(jax.devices()) < world:
        return {"skipped": f"need {world} devices, have "
                           f"{len(jax.devices())}"}
    f = make_residual_jacobian_fn(mode=base_option.jacobian_mode)

    def opt_for(mesh2d: bool):
        return _dc.replace(
            base_option, world_size=world,
            solver_option=_dc.replace(
                base_option.solver_option, mesh_2d=mesh2d,
                cam_blocks=cam_blocks if mesh2d else 0))

    def run(label, mesh2d):
        opt = opt_for(mesh2d)
        kw = dict(use_tiled=False, timer=timer)
        # Census FIRST: the lower_only compile primes the persistent
        # cache, so the warm solve below pays the trace but not a
        # second XLA compile (the census itself would otherwise be a
        # third full compile-path round trip per side).
        lowered = flat_solve(f, s.cameras0, s.points0, s.obs, s.cam_idx,
                             s.pt_idx, opt, use_tiled=False,
                             lower_only=True)
        ops = hlo_mod.parse_compiled_ops(lowered.compile().as_text())
        with timer.phase(f"mesh2d_warm_{label}"):
            flat_solve(f, s.cameras0, s.points0, s.obs, s.cam_idx,
                       s.pt_idx, opt, **kw)
        t0 = time.perf_counter()
        with timer.phase(f"mesh2d_solve_{label}"):
            res = flat_solve(f, s.cameras0, s.points0, s.obs, s.cam_idx,
                             s.pt_idx, opt, **kw)
            # Dispatch is async: without this the cheaper side can
            # report its enqueue time, not its solve time.
            jax.block_until_ready(res)
        elapsed = time.perf_counter() - t0
        body, census, bytes_moved = pcg_body_collective_summary(ops, world)
        return res, {
            "elapsed_s": round(elapsed, 3),
            "lm_iters": int(res.iterations),
            "pcg_iters": int(res.pcg_iterations),
            "collective_bytes_per_sp": round(bytes_moved, 1),
            "pcg_body_census": census,
            "pcg_body_group_sizes": sorted(
                {op.group_size(world) or world for op in body}),
        }

    res1, side1 = run("1d", mesh2d=False)
    res2, side2 = run("2d", mesh2d=True)
    # Cache hit by construction: the 2-D flat_solve above planned the
    # identical geometry through the same fingerprint LRU.
    (plan, _), _ = cached_camera_tile_plan(
        s.cam_idx, s.pt_idx, len(s.cameras0), len(s.points0),
        edge_shards, cam_blocks)
    rel_gap = abs(float(res2.cost) - float(res1.cost)) / max(
        float(res1.cost), 1e-30)
    result = {
        "lane": _device_tag(),
        "mesh": f"{edge_shards}x{cam_blocks}",
        "world_size": world,
        "scene": {"cameras": len(s.cameras0), "points": len(s.points0),
                  "edges": int(s.obs.shape[0])},
        "one_d": side1,
        "two_d": side2,
        "bytes_per_sp_ratio_2d_vs_1d": round(
            side2["collective_bytes_per_sp"]
            / max(side1["collective_bytes_per_sp"], 1e-30), 4),
        "tile_plan": {
            "cam_blocks": plan.cam_blocks,
            "tile_cams": plan.tile_cams,
            "shard_points": plan.shard_points,
            "tiles_per_matvec": plan.cam_blocks,  # the C-step loop
            "edges_padded": plan.n_edges_padded,
            "bucket_width": plan.bucket_width,
            "reuse": plan.reuse,
        },
        "final_cost_rel_gap": rel_gap,
    }
    artifact_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_mesh2d.json")
    with open(artifact_path, "w") as fh:
        json.dump(result, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return result


def bf16_head_to_head(s, base_option, timer) -> dict:
    """bf16 MXU pipeline vs f32 under the production inexact-LM config
    (MEGBA_BENCH_BF16=1): the same scene, forcing + warm starts, PR 5's
    guards ARMED on both sides — the contract is that bf16 converges
    within the documented cost-gap band of the f32 control with ZERO
    guard/recovery events (a clean bf16 run must not lean on the
    containment machinery), certified in BENCH_bf16.json together with
    the structurally-pinned halved bytes axis.

    HONESTY TAG: this lane is CPU — XLA:CPU float-normalizes bf16
    compute to f32-with-converts, so wall-clock here measures the
    CONVERT OVERHEAD, not the MXU/bandwidth win; the transferable
    evidence is the cost-parity curve and the auditor's structural
    axes (bf16-only dot operands with f32 accumulation, and
    collective_bytes_per_sp at exactly half the f32 programs' —
    ba_bf16_w2_f32 is re-audited LIVE here, the committed
    ANALYSIS_BUDGET.json supplies the 2-D pair).
    """
    import dataclasses as _dc

    import jax

    from megba_tpu.common import RobustOption, SolverOption
    from megba_tpu.observability.report import _decode_fallback_totals
    from megba_tpu.ops.residuals import make_residual_jacobian_fn
    from megba_tpu.solve import flat_solve

    f = make_residual_jacobian_fn(mode=base_option.jacobian_mode)

    def opt_for(bf16: bool):
        return _dc.replace(
            base_option,
            robust_option=RobustOption(guards=True),
            solver_option=SolverOption(
                max_iter=PCG_ITERS, refuse_ratio=1e30,
                forcing=True, warm_start=True, bf16=bf16))

    def run(label, bf16):
        opt = opt_for(bf16)
        kw = dict(use_tiled=False, timer=timer)
        with timer.phase(f"bf16_warm_{label}"):
            jax.block_until_ready(
                flat_solve(f, s.cameras0, s.points0, s.obs, s.cam_idx,
                           s.pt_idx, opt, **kw).cost)
        t0 = time.perf_counter()
        with timer.phase(f"bf16_solve_{label}"):
            res = flat_solve(f, s.cameras0, s.points0, s.obs, s.cam_idx,
                             s.pt_idx, opt, **kw)
            jax.block_until_ready(res)
        elapsed = time.perf_counter() - t0
        iters = int(res.iterations)
        trace = res.trace
        level = _decode_fallback_totals(trace, iters) or {}
        return res, {
            "elapsed_s": round(elapsed, 3),
            "lm_iters": iters,
            "accepted": int(res.accepted),
            "pcg_iters_total": int(res.pcg_iterations),
            "cost": float(res.cost),
            "status": _status_name(res),
            # Decoded guard/recovery evidence: LM-contained recoveries,
            # in-loop PCG breakdown restarts, and per-level
            # preconditioner fallbacks — all must be ZERO on a clean
            # run, bf16 included.
            "recoveries": int(res.recoveries),
            "pcg_breakdowns": int(np.asarray(
                trace.pcg_breakdown[:iters]).sum()),
            "precond_fallbacks": dict(level),
        }

    res32, side32 = run("f32", bf16=False)
    resbf, sidebf = run("bf16", bf16=True)
    gap = abs(sidebf["cost"] - side32["cost"]) / max(
        abs(side32["cost"]), 1e-30)

    # Structural axes via the auditor: live w2 pair (cheap tiny
    # programs, persistent compile cache), committed budget for the
    # 2-D pair.
    audited = {}
    if len(jax.devices()) >= 2:
        from megba_tpu.analysis import program_audit

        specs = program_audit.program_specs()
        for name in ("ba_sharded_w2_f32", "ba_bf16_w2_f32"):
            with timer.phase(f"bf16_audit_{name}"):
                a = program_audit.audit_program(specs[name])
            audited[name] = {
                "collective_bytes_per_sp": a.metrics()[
                    "collective_bytes_per_sp"],
                "violations": a.violations(),
            }
    from megba_tpu.analysis import budget as budget_mod

    committed = budget_mod.load_baseline()
    committed_axis = {
        name: committed.get(name, {}).get("collective_bytes_per_sp")
        for name in ("ba_sharded_w2_f32", "ba_bf16_w2_f32",
                     "ba_2d_w4_f32", "ba_bf16_2d_w4_f32")}

    result = {
        "lane": _device_tag(),
        "config": "inexact-LM (forcing + warm starts), guards armed, "
                  f"pcg_max_iter={PCG_ITERS}",
        "scene": {"cameras": len(s.cameras0), "points": len(s.points0),
                  "edges": int(s.obs.shape[0])},
        "f32": side32,
        "bf16": sidebf,
        "cost_rel_gap": gap,
        # The documented acceptance band (ARCHITECTURE.md "Precision
        # ladder"): the bf16 operator carries ~eps_bf16-class accuracy,
        # and the inexact-LM trajectory resolves the OPERATOR, not the
        # arithmetic — venice-class scenes land well inside 2e-2.
        "cost_gap_band": 2e-2,
        "pcg_iters_delta": (sidebf["pcg_iters_total"]
                            - side32["pcg_iters_total"]),
        "guard_events_bf16": (sidebf["recoveries"]
                              + sidebf["pcg_breakdowns"]),
        "audited_live": audited,
        "committed_bytes_per_sp": committed_axis,
    }
    artifact_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_bf16.json")
    with open(artifact_path, "w") as fh:
        json.dump(result, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return result


def fused_head_to_head(s, base_option, timer) -> dict:
    """Fused edge-pipeline kernels vs the tiled XLA lowering
    (MEGBA_BENCH_FUSED=1): the same scene on the SAME tiled edge plans,
    production inexact-LM config, guards ARMED on both sides — the
    contract is end-to-end LM cost parity within 1e-5 with ZERO
    guard/recovery events (a clean fused run must not lean on the
    containment machinery), plus the structural bytes story: the
    per-S·p HBM budget with and without the transient gather/product
    round-trips the fusion deletes, priced live for this scene by
    analysis/edge_budget and pinned for the canonical programs in
    ANALYSIS_BUDGET.json.

    HONESTY TAG: off-TPU the Pallas kernels run under INTERPRET mode —
    wall-clock here measures the Python-level kernel interpreter (orders
    of magnitude slower than both XLA:CPU and the Mosaic lowering), so
    the fused side's elapsed time is NOT evidence of the VMEM-residency
    win and no speedup ratio is reported from this lane.  The
    transferable evidence is the cost-parity band, the zero-guard
    certificate, and the analytical bytes_touched_per_sp delta.
    """
    import dataclasses as _dc
    import tempfile

    import jax

    from megba_tpu.common import RobustOption, SolverOption
    from megba_tpu.ops.residuals import make_residual_jacobian_fn
    from megba_tpu.solve import flat_solve

    f = make_residual_jacobian_fn(mode=base_option.jacobian_mode)
    tele = tempfile.NamedTemporaryFile(
        mode="r", suffix=".jsonl", delete=False)
    tele.close()

    def opt_for(fused: bool):
        return _dc.replace(
            base_option,
            robust_option=RobustOption(guards=True),
            telemetry=tele.name,
            solver_option=SolverOption(
                max_iter=PCG_ITERS, refuse_ratio=1e30,
                forcing=True, warm_start=True, fused_kernels=fused))

    def run(label, fused):
        opt = opt_for(fused)
        kw = dict(use_tiled=True, timer=timer)
        with timer.phase(f"fused_warm_{label}"):
            jax.block_until_ready(
                flat_solve(f, s.cameras0, s.points0, s.obs, s.cam_idx,
                           s.pt_idx, opt, **kw).cost)
        t0 = time.perf_counter()
        with timer.phase(f"fused_solve_{label}"):
            res = flat_solve(f, s.cameras0, s.points0, s.obs, s.cam_idx,
                             s.pt_idx, opt, **kw)
            jax.block_until_ready(res)
        elapsed = time.perf_counter() - t0
        iters = int(res.iterations)
        return res, {
            "elapsed_s": round(elapsed, 3),
            "lm_iters": iters,
            "accepted": int(res.accepted),
            "pcg_iters_total": int(res.pcg_iterations),
            "cost": float(res.cost),
            "status": _status_name(res),
            "recoveries": int(res.recoveries),
            "pcg_breakdowns": int(np.asarray(
                res.trace.pcg_breakdown[:iters]).sum()),
        }

    res32, side_xla = run("xla", fused=False)
    resf, side_fused = run("pallas", fused=True)
    gap = abs(side_fused["cost"] - side_xla["cost"]) / max(
        abs(side_xla["cost"]), 1e-30)

    # Per-solve tile/reuse metrics ride the telemetry report; the last
    # line is the fused run.
    tiles = None
    try:
        lines = [ln for ln in open(tele.name) if ln.strip()]
        if lines:
            tiles = json.loads(lines[-1]).get("tiles")
    finally:
        os.unlink(tele.name)

    # The structural half: price this scene's per-S·p HBM bytes with
    # the transient gather/product round-trips (tiled XLA lowering) and
    # without them (fused kernels) — same plan, same dtype surface, so
    # the delta IS the traffic the fusion deletes.
    from megba_tpu.analysis import budget as budget_mod
    from megba_tpu.analysis import edge_budget
    from megba_tpu.ops.segtiles import cached_dual_plans

    (plan_c, _plans), _ = cached_dual_plans(
        np.asarray(s.cam_idx), np.asarray(s.pt_idx),
        len(s.cameras0), len(s.points0))
    geom = dict(num_cameras=len(s.cameras0), cd=9,
                num_points=len(s.points0), pd=3, rd=2,
                edge_slots=plan_c.n_slots)
    arm_xla = edge_budget.schur_sp_budget(**geom, transient_roundtrips=True)
    arm_fused = edge_budget.schur_sp_budget(**geom,
                                            transient_roundtrips=False)
    committed = budget_mod.load_baseline()
    committed_axes = {
        name: {k: committed.get(name, {}).get(k)
               for k in ("flops_per_sp", "bytes_touched_per_sp")}
        for name in ("ba_tiled_f32", "ba_bf16_w2_f32")}

    result = {
        "lane": _device_tag(),
        "config": "inexact-LM (forcing + warm starts), guards armed, "
                  f"pcg_max_iter={PCG_ITERS}, tiled plans both sides",
        "scene": {"cameras": len(s.cameras0), "points": len(s.points0),
                  "edges": int(s.obs.shape[0])},
        "xla_tiled": side_xla,
        "fused_pallas": side_fused,
        "cost_rel_gap": gap,
        # The ISSUE acceptance band: end-to-end LM cost within 1e-5 of
        # the unfused lowering with zero guard events.
        "cost_gap_band": 1e-5,
        "guard_events_fused": (side_fused["recoveries"]
                               + side_fused["pcg_breakdowns"]),
        "tiles": tiles,
        "bytes_per_sp_with_transients": arm_xla["bytes_touched_per_sp"],
        "bytes_per_sp_fused": arm_fused["bytes_touched_per_sp"],
        "transient_bytes_deleted_per_sp": (
            arm_xla["bytes_touched_per_sp"]
            - arm_fused["bytes_touched_per_sp"]),
        "flops_per_sp": arm_fused["flops_per_sp"],
        "committed_axes": committed_axes,
    }
    artifact_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_fused.json")
    with open(artifact_path, "w") as fh:
        json.dump(result, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return result


class BenchScene(NamedTuple):
    """The synthetic scene of one bench run and the solve set up on it."""

    s: object             # io/synthetic.SyntheticBAL
    option: object        # ProblemOption of the throughput pass
    dtype: object
    locality: object      # MEGBA_BENCH_LOCALITY, None = expander scene
    tiled: bool           # tiled lowering (ops/segtiles) taken
    args: tuple           # device operands of the lm_solve program
    cam_idx_p: object     # host edge arrays in the solve's edge order
    pt_idx_p: object
    mask: object
    timed_solve: object   # (option, label, cluster_plan=None) -> (res, s)


def setup_scene(timer) -> BenchScene:
    """Build the configured scene and its compiled-solve closure.

    Shared by main() (on the chip) and scripts/bench_gates.py (the CPU
    acceptance gates of the head-to-heads); it runs on whatever backend
    the caller has checked.
    """
    import jax
    import jax.numpy as jnp

    from megba_tpu.common import (
        AlgoOption,
        ComputeKind,
        JacobianMode,
        ProblemOption,
        SolverOption,
    )
    from megba_tpu.algo import lm_solve
    from megba_tpu.io.synthetic import make_synthetic_bal
    from megba_tpu.ops.residuals import make_residual_jacobian_fn

    if _C.dtype == "float64":
        jax.config.update("jax_enable_x64", True)
    dtype = np.dtype(_C.dtype)
    jac_mode = JacobianMode[_C.jacobian]
    compute_kind = ComputeKind[_C.compute]

    # MEGBA_BENCH_LOCALITY=ring|grid swaps the expander observation
    # assignment for a locality-structured scene (banded camera
    # co-observation — the structure real BAL graphs have and the
    # camera-graph coarse-space preconditioners need; see
    # io/synthetic.py).  Default: the historical expander scene.
    locality = os.environ.get("MEGBA_BENCH_LOCALITY") or None
    s = make_synthetic_bal(
        num_cameras=NUM_CAMERAS,
        num_points=NUM_POINTS,
        obs_per_point=OBS_PER_POINT,
        seed=0,
        param_noise=1e-2,
        pixel_noise=0.5,
        dtype=dtype,
        locality=locality,
    )

    option = ProblemOption(
        dtype=dtype,
        compute_kind=compute_kind,
        jacobian_mode=jac_mode,
        mixed_precision_pcg=_C.mixed,
        algo_option=AlgoOption(max_iter=LM_ITERS, epsilon1=1e-12, epsilon2=1e-15),
        solver_option=SolverOption(max_iter=PCG_ITERS, tol=1e-10, refuse_ratio=1e30),
    )
    f = make_residual_jacobian_fn(mode=jac_mode)

    # Feature-major tiled lowering (ops/segtiles.py): the dual-plan slot
    # order replaces the camera sort + quantum padding, and every
    # segment reduction / expansion in the solver becomes a block-aligned
    # MXU one-hot matmul (scatter-free).  TPU + float32 only: on the CPU
    # (the bench_gates lane) the tiled plan's XLA lowering is slower AND
    # fatter than the chunked scatter-add build (the r02 regression).
    # f64 (ladybug) always keeps the classic chunked path.
    from megba_tpu.core.fm import EDGE_QUANTUM
    from megba_tpu.core.types import is_cam_sorted, pad_edges

    from megba_tpu.solve import default_use_tiled

    tiled = default_use_tiled(dtype)
    plans = None
    if tiled:
        from megba_tpu.ops.segtiles import cached_dual_plans, probe_kernels

        # Host plan cache (ops/segtiles.py): bench reruns in one process
        # (and the production flat_solve path) reuse the ~270 ms plan
        # build; hits are counted into the phase breakdown.
        with timer.phase("plan"):
            (plan_c, plans), plan_hit = cached_dual_plans(
                s.cam_idx, s.pt_idx, NUM_CAMERAS, NUM_POINTS,
                use_kernels=probe_kernels())
            if plan_hit:
                timer.count_event("plan_cache_hit")
        perm, pmask = plan_c.perm, plan_c.mask
        obs_p = s.obs[perm] * pmask[:, None].astype(dtype)
        cam_idx_p = plan_c.seg
        pt_idx_p = np.where(pmask > 0, s.pt_idx[perm], 0).astype(np.int32)
        mask = pmask.astype(dtype)
        cam_sorted = True
    else:
        obs_p, cam_idx_p, pt_idx_p, mask = pad_edges(
            s.obs, s.cam_idx, s.pt_idx, EDGE_QUANTUM, dtype=dtype)
        cam_sorted = is_cam_sorted(s.cam_idx)
    args = (
        jnp.asarray(s.cameras0.T),
        jnp.asarray(s.points0.T),
        jnp.asarray(np.ascontiguousarray(obs_p.T)),
        jnp.asarray(cam_idx_p),
        jnp.asarray(pt_idx_p),
        jnp.asarray(mask),
    )

    def timed_solve(opt, label, cluster_plan=None):
        solve = jax.jit(
            lambda cams, pts, obs, ci, pi, m, pl, cp: lm_solve(
                f, cams, pts, obs, ci, pi, m, opt, cam_sorted=cam_sorted,
                plans=pl, cluster_plan=cp)
        )
        # Warmup (compile) — not part of the metric, but recorded as a
        # phase so the compile cost is visible in the artifact.
        with timer.phase(f"compile_{label}") as ph:
            ph.sync(solve(*args, plans, cluster_plan).cost)
        t0 = time.perf_counter()
        with timer.phase(f"solve_{label}") as ph:
            res = ph.sync(solve(*args, plans, cluster_plan))
        return res, time.perf_counter() - t0

    return BenchScene(s, option, dtype, locality, tiled, args, cam_idx_p,
                      pt_idx_p, mask, timed_solve)


def head_to_heads(sc: BenchScene, res, elapsed, timer) -> dict:
    """Run the head-to-heads the MEGBA_BENCH_* knobs select against the
    throughput pass `res` (wall `elapsed`); None for each one not asked
    for.  main() puts them under `extra`, scripts/bench_gates.py asserts
    their contracts on the CPU."""
    from megba_tpu.common import SolverOption

    (s, option, dtype, locality, _tiled, _args, cam_idx_p, pt_idx_p, mask,
     timed_solve) = sc
    # Inexact-LM head-to-head (MEGBA_BENCH_FORCING=1): the same LM
    # budget with adaptive Eisenstat-Walker forcing + PCG warm starts
    # (SolverOption(forcing=True, warm_start=True)) vs the fixed
    # tight-tolerance regime above (tol=1e-10, cold starts — the
    # configuration FINAL_CONVERGENCE.json / the throughput pass run,
    # and the waste ISSUE 4 targets: every LM iteration pays ~30 PCG
    # iterations regardless of how inaccurate its linearization is).
    # Contract: total PCG iterations down >= 30%, final cost unmoved
    # within the curve gap_tol (scripts/run_tests.sh asserts it).
    forcing_cmp = None
    if os.environ.get("MEGBA_BENCH_FORCING") == "1":
        import dataclasses as _dcf

        forcing_option = _dcf.replace(option, solver_option=SolverOption(
            max_iter=PCG_ITERS, refuse_ratio=1e30,
            forcing=True, warm_start=True))
        f_res, f_elapsed = timed_solve(forcing_option, "forcing")
        base_pcg = int(res.pcg_iterations)
        f_pcg = int(f_res.pcg_iterations)
        base_cost = float(res.cost)
        forcing_cmp = {
            "lm_iters": int(f_res.iterations),
            "accepted": int(f_res.accepted),
            "pcg_iters_total": f_pcg,
            "pcg_iters_total_fixed_tol": base_pcg,
            "pcg_reduction": round(1.0 - f_pcg / max(base_pcg, 1), 4),
            "cost": float(f_res.cost),
            "cost_fixed_tol": base_cost,
            "cost_rel_gap": round(
                abs(float(f_res.cost) - base_cost)
                / max(abs(base_cost), 1e-30), 6),
            "elapsed_s": round(f_elapsed, 3),
            "speedup_vs_fixed_tol": round(elapsed / f_elapsed, 3),
        }
    # Preconditioner head-to-head (MEGBA_BENCH_PRECOND=<kind>): the
    # SAME inexact-LM production config (forcing + warm starts — the
    # regime PR 4 made the default optimum) solved twice, differing
    # ONLY in the preconditioner operator family, so the comparison
    # attributes iterations and wall-clock to the operator and nothing
    # else.  This is the ISSUE 7 plateau observable: total PCG
    # iterations, relative final-cost gap, wall-clock ratio, and the
    # per-inner-iteration cost delta (= what one stronger apply costs).
    # MEGBA_BENCH_CLUSTERS / MEGBA_BENCH_NEUMANN_ORDER tune the knobs.
    precond_cmp = None
    precond_kind_env = os.environ.get("MEGBA_BENCH_PRECOND", "")
    if precond_kind_env:
        import dataclasses as _dcp

        from megba_tpu.common import PrecondKind

        cand_kind = PrecondKind[precond_kind_env.upper()]
        n_clusters = int(os.environ.get("MEGBA_BENCH_CLUSTERS", "0") or "0")
        n_order = int(os.environ.get("MEGBA_BENCH_NEUMANN_ORDER", "1"))
        # Hierarchy / smoothed-aggregation knobs (MULTILEVEL /
        # TWO_LEVEL): per-level coarsening factor, total level cap,
        # prolongator smoothing weight.
        coarsen = float(os.environ.get("MEGBA_BENCH_COARSEN", "4.0"))
        n_levels = int(os.environ.get("MEGBA_BENCH_LEVELS", "3"))
        sm_omega = float(os.environ.get("MEGBA_BENCH_SMOOTH_OMEGA", "0.0"))
        base_opt = _dcp.replace(option, solver_option=SolverOption(
            max_iter=100, refuse_ratio=1e30, forcing=True, warm_start=True))
        cand_opt = _dcp.replace(option, solver_option=SolverOption(
            max_iter=100, refuse_ratio=1e30, forcing=True, warm_start=True,
            precond=cand_kind, neumann_order=n_order,
            coarse_clusters=n_clusters, coarsen_factor=coarsen,
            max_levels=n_levels, smooth_omega=sm_omega))
        cand_cluster_plan = None
        hierarchy_levels = None
        if cand_kind == PrecondKind.TWO_LEVEL:
            from megba_tpu.ops.segtiles import cached_cluster_plan

            with timer.phase("plan"):
                (_, cand_cluster_plan), _hit = cached_cluster_plan(
                    np.asarray(cam_idx_p), np.asarray(pt_idx_p),
                    NUM_CAMERAS, NUM_POINTS, n_clusters,
                    mask=np.asarray(mask), smooth_omega=sm_omega)
            hierarchy_levels = 2
        elif cand_kind == PrecondKind.MULTILEVEL:
            from megba_tpu.ops.segtiles import cached_multilevel_plan

            with timer.phase("plan"):
                (mplan, cand_cluster_plan), _hit = cached_multilevel_plan(
                    np.asarray(cam_idx_p), np.asarray(pt_idx_p),
                    NUM_CAMERAS, NUM_POINTS, n_clusters,
                    mask=np.asarray(mask), coarsen_factor=coarsen,
                    max_levels=n_levels, smooth_omega=sm_omega)
            # fine level + every planned coarse level
            hierarchy_levels = 1 + len(mplan.level_sizes)
        p_base, p_base_s = timed_solve(base_opt, "precond_base")
        p_cand, p_cand_s = timed_solve(cand_opt, "precond_cand",
                                       cluster_plan=cand_cluster_plan)
        # Per-level fallback totals decoded from the candidate's trace
        # (solver/precond.py enum codes): the head-to-head artifact
        # records whether the stronger operator actually ran its full
        # hierarchy or spent iterations degraded.
        from megba_tpu.observability.report import _decode_fallback_totals

        cand_fallback = _decode_fallback_totals(
            p_cand.trace, int(p_cand.iterations))
        b_pcg, c_pcg = int(p_base.pcg_iterations), int(p_cand.pcg_iterations)
        b_cost = float(p_base.cost)
        b_iter_ms = 1000.0 * p_base_s / max(b_pcg, 1)
        c_iter_ms = 1000.0 * p_cand_s / max(c_pcg, 1)
        precond_cmp = {
            "kind": cand_kind.name.lower(),
            "baseline_kind": "jacobi",
            "locality": locality,
            "coarse_clusters": n_clusters,
            "neumann_order": n_order,
            "coarsen_factor": coarsen,
            "max_levels": n_levels,
            "smooth_omega": sm_omega,
            "hierarchy_levels": hierarchy_levels,
            "fallback": cand_fallback,
            "pcg_iters_total": c_pcg,
            "pcg_iters_total_jacobi": b_pcg,
            "pcg_reduction": round(1.0 - c_pcg / max(b_pcg, 1), 4),
            "cost": float(p_cand.cost),
            "cost_jacobi": b_cost,
            "cost_rel_gap": round(
                abs(float(p_cand.cost) - b_cost) / max(abs(b_cost), 1e-30),
                6),
            "elapsed_s": round(p_cand_s, 3),
            "elapsed_s_jacobi": round(p_base_s, 3),
            "speedup_vs_jacobi": round(p_base_s / p_cand_s, 3),
            # Per-inner-iteration wall cost (one precond apply + one
            # S·p each): the delta is what the stronger apply costs.
            "pcg_iter_ms": round(c_iter_ms, 3),
            "pcg_iter_ms_jacobi": round(b_iter_ms, 3),
            "precond_apply_extra_ms": round(c_iter_ms - b_iter_ms, 3),
        }
    # Fleet head-to-head (MEGBA_BENCH_FLEET=<n>): n heterogeneous small
    # problems (io/synthetic.make_fleet) solved as a serial flat_solve
    # loop vs one batched solve_many pass (serving/batcher.py), both
    # warmed first so the metric is steady-state problems/sec at fixed
    # convergence — the roadmap's fleet throughput observable — not
    # compile amortisation.  scripts/run_tests.sh asserts batched > serial
    # and a terminal per-lane SolveStatus.
    fleet_cmp = None
    n_fleet = int(os.environ.get("MEGBA_BENCH_FLEET", "0") or "0")
    if n_fleet:
        fleet_cmp = fleet_head_to_head(n_fleet, dtype, timer)
    # Federation head-to-head (MEGBA_BENCH_FEDERATION=<n_workers>): the
    # scale-OUT complement — an n-worker FleetRouter (worker processes
    # warmed from serialized artifacts) vs the single-host FleetQueue on
    # the same fleet, plus the replica cold-start split (artifact-load
    # vs compile-from-scratch, zero-trace certified).  Also written to
    # BENCH_federation.json as a standalone artifact.
    federation_cmp = None
    n_fed = int(os.environ.get("MEGBA_BENCH_FEDERATION", "0") or "0")
    if n_fed:
        federation_cmp = federation_head_to_head(n_fed, dtype, timer)
    # 2-D mesh head-to-head (MEGBA_BENCH_MESH2D=<ExC>): the 2-D
    # camera x edge distribution vs 1-D edge sharding at the same world
    # size — bytes-moved per CG step, subgroup census, tile/reuse
    # geometry, and (CPU-lane-tagged) wall-clock.  Also written to
    # BENCH_mesh2d.json as a standalone artifact.
    mesh2d_cmp = None
    if _MESH2D_SPEC:
        mesh2d_cmp = mesh2d_head_to_head(s, option, _E2D, _C2D, timer)
    # bf16 MXU pipeline head-to-head (MEGBA_BENCH_BF16=1): f32 vs bf16
    # under the production inexact-LM config with guards armed — cost
    # parity band, PCG-iteration delta, decoded guard/recovery counts
    # (must be zero on the clean run), and the auditor's halved
    # collective_bytes_per_sp axes.  Also written to BENCH_bf16.json.
    bf16_cmp = None
    if _BF16_BENCH:
        bf16_cmp = bf16_head_to_head(s, option, timer)
    # Fused edge-pipeline head-to-head (MEGBA_BENCH_FUSED=1): Pallas
    # mega-kernels vs the tiled XLA lowering on the same plans — cost
    # parity band, zero-guard certificate, tile/reuse geometry, and the
    # analytical transient-bytes-deleted axis (interpret-mode
    # honesty-tagged off-TPU).  Also written to BENCH_fused.json.
    fused_cmp = None
    if _FUSED_BENCH:
        fused_cmp = fused_head_to_head(s, option, timer)
    # Observability-plane overhead head-to-head (MEGBA_BENCH_OBS=1):
    # solve_many with the plane off vs metrics+spans on, same warmed
    # fleet, <= 2% acceptance band.  Also written to BENCH_obs.json.
    obs_cmp = None
    if _OBS_BENCH:
        obs_cmp = obs_head_to_head(max(n_fleet, 8), dtype, timer)
    return {"locality": locality, "forcing": forcing_cmp,
            "precond": precond_cmp, "fleet": fleet_cmp,
            "federation": federation_cmp, "mesh2d": mesh2d_cmp,
            "bf16": bf16_cmp, "fused": fused_cmp, "obs": obs_cmp}


def main() -> None:
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from megba_tpu.utils.backend import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    import jax

    if _C.force_cpu:
        # This config is CPU by design (the reference measures its
        # BAL_Double example CPU-side too).
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit(
            f"bench config {CONFIG!r} runs on a TPU; JAX found no TPU "
            f"({_device_tag()})")

    from megba_tpu.common import SolverOption
    # Phase breakdown (utils/timing.PhaseTimer) rides the JSON line so
    # committed BENCH_*.json artifacts carry per-phase wall clocks, and
    # feeds the optional SolveReport below.
    from megba_tpu.utils.timing import PhaseTimer

    timer = PhaseTimer()

    sc = setup_scene(timer)
    s, option, dtype, locality, tiled, args = sc[:6]
    n_edge = s.obs.shape[0]
    dtype_name, jac_name, ck_name = _C.dtype, _C.jacobian, _C.compute
    mixed = _C.mixed
    timed_solve = sc.timed_solve

    res, elapsed = timed_solve(option, "throughput")
    iters = int(res.iterations)
    lm_iters_per_sec = iters / elapsed

    # Convergence-mode pass: the reference's DEFAULT solver flags
    # (common.h:27-33 — tol=1e-1, refuse_ratio=1.0), the regime
    # BASELINE.md's cost-vs-time metric is defined in.  The throughput
    # pass above (tol=1e-10) does near-fixed work per LM iteration; this
    # one measures the time-to-quality observable.  It is a second
    # compiled program; MEGBA_BENCH_CONVERGENCE=0 skips it when the
    # accelerator window is too precious for a second large compile.
    conv = None
    if os.environ.get("MEGBA_BENCH_CONVERGENCE", "1") != "0":
        import dataclasses as _dc

        conv_option = _dc.replace(option, solver_option=SolverOption())
        conv_res, conv_elapsed = timed_solve(conv_option, "convergence")
        conv_iters = int(conv_res.iterations)
        conv_pcg = int(conv_res.pcg_iterations)
        conv = {
            "lm_iters_per_sec": round(conv_iters / conv_elapsed, 3),
            "lm_iters": conv_iters,
            "accepted": int(conv_res.accepted),
            "pcg_iters_per_lm": round(
                float(conv_res.pcg_iterations) / max(conv_iters, 1), 2),
            # Plateau-metric context (ISSUE 7): WHICH preconditioner
            # operator produced this pcg_iters_per_lm, and what one
            # inner iteration costs wall-clock (each fused PCG
            # iteration performs exactly one precond apply + one S·p,
            # so this is the per-apply cost ceiling) — tracked in the
            # artifact instead of only in round prose.
            "precond": conv_option.solver_option.precond.name.lower(),
            "pcg_iter_ms": round(
                1000.0 * conv_elapsed / max(conv_pcg, 1), 3),
            "cost_reduction": round(
                float(conv_res.initial_cost)
                / max(float(conv_res.cost), 1e-30), 3),
            "elapsed_s": round(conv_elapsed, 3),
        }
    cmp = head_to_heads(sc, res, elapsed, timer)
    # Charge the reference model the S·p products this run actually
    # executed (the PCG can exit below the 30-iteration cap), so both
    # sides of vs_baseline do the same algorithmic work: the fused PCG
    # body performs one matvec per iteration.
    measured_pcg_per_lm = float(res.pcg_iterations) / max(iters, 1)
    baseline = derived_baseline_lm_iters_per_sec(
        n_edge=n_edge,
        n_cam=NUM_CAMERAS,
        n_pt=NUM_POINTS,
        pcg_iters=measured_pcg_per_lm,
        ref_dtype_bytes=_C.ref_dtype_bytes,
        implicit=_C.ref_implicit,
    )
    backend = jax.default_backend()
    vs_baseline = round(lm_iters_per_sec / baseline, 3)
    # Opt-in compiled-program audit embed (MEGBA_BENCH_AUDIT=1): the
    # static census of the canonical CPU-lowered programs rides the
    # bench line, so a committed BENCH_*.json can show a perf move next
    # to the collective/FLOP-budget story of the same tree.  Off by
    # default — it costs extra CPU lowers/compiles inside a possibly
    # precious accelerator window.
    audit_summaries = None
    if os.environ.get("MEGBA_BENCH_AUDIT") == "1":
        # Context rides with the summaries: unlike the CLI gate, this
        # embed lowers on THIS process's backend and x64 setting — on a
        # TPU backend with x64 off the dtype census is vacuous and the
        # cost metrics are not comparable to the (CPU, x64-on)
        # ANALYSIS_BUDGET.json.  `audit --check` is the gate; this is
        # the bench line's descriptive snapshot, labeled as such.
        # An embed that fails ends the run nonzero: no result line
        # carries a silently missing phase.
        from megba_tpu.analysis import program_audit

        # The SPMD program needs a 2-device mesh; a single-device
        # bench topology audits just the single-device program (the
        # audit CLI lane always forces >= 2 virtual CPU devices).
        names = ["ba_single_f32"]
        if len(jax.devices()) >= 2:
            names.append("ba_sharded_w2_f32")
        audit_summaries = {
            "backend": backend,
            "x64": bool(jax.config.jax_enable_x64),
            "gate": "python -m megba_tpu.analysis.audit --check",
            "programs": {
                name: audit.summary()
                for name, audit in program_audit.audit_all(names).items()
            },
        }
    print(
        json.dumps(
            {
                "metric": (
                    f"LM iters/sec, synthetic {CONFIG} "
                    f"({NUM_CAMERAS} cams / {NUM_POINTS} pts / {n_edge} edges, "
                    f"{measured_pcg_per_lm:.1f} PCG iters/LM), "
                    f"{dtype_name} {jac_name.lower()} {ck_name.lower()}"
                    f"{' bf16-mixed' if mixed else ''}"
                    f"{f' locality={locality}' if locality else ''}, "
                    f"1 chip [{_device_tag()}]"
                ),
                "value": round(lm_iters_per_sec, 3),
                "unit": "LM iters/s",
                "vs_baseline": vs_baseline,
                "extra": {
                    "backend": backend,
                    # Scene structure (MEGBA_BENCH_LOCALITY): None =
                    # the historical expander assignment.
                    "locality": locality,
                    # Termination semantics (common.SolveStatus): a
                    # driver reading this line can tell a converged
                    # number from a stalled or recovered one.
                    "status": _status_name(res),
                    "tiled_engine": bool(tiled),
                    "lm_iter_ms": round(1000.0 * elapsed / iters, 3),
                    "pcg_iters_per_lm": round(measured_pcg_per_lm, 2),
                    "pcg_iters_per_sec": round(
                        lm_iters_per_sec * measured_pcg_per_lm, 1),
                    "derived_baseline_lm_iters_per_sec": round(baseline, 3),
                    "baseline_model": "A100-40GB roofline, BASELINE.md",
                    # Reference-default flags (tol=1e-1, refuse_ratio=1):
                    # the time-to-quality regime of BASELINE.md's metric.
                    "convergence_mode": conv,
                    # Inexact-LM head-to-head (MEGBA_BENCH_FORCING=1):
                    # forcing+warm_start vs the fixed tight-tol regime.
                    "forcing": cmp["forcing"],
                    # Preconditioner head-to-head
                    # (MEGBA_BENCH_PRECOND=<kind>): the candidate
                    # operator vs block-Jacobi under the same
                    # inexact-LM config.
                    "precond": cmp["precond"],
                    # Fleet head-to-head (MEGBA_BENCH_FLEET=<n>):
                    # batched solve_many vs serial flat_solve loop.
                    "fleet": cmp["fleet"],
                    # Federation head-to-head
                    # (MEGBA_BENCH_FEDERATION=<n_workers>): n-worker
                    # router vs single-host FleetQueue + cold-start
                    # split; also lands in BENCH_federation.json.
                    "federation": cmp["federation"],
                    # 2-D mesh head-to-head (MEGBA_BENCH_MESH2D=<ExC>):
                    # subgroup-collective bytes-moved + tile/reuse
                    # geometry vs 1-D; also lands in BENCH_mesh2d.json.
                    "mesh2d": cmp["mesh2d"],
                    # bf16 MXU pipeline head-to-head
                    # (MEGBA_BENCH_BF16=1): cost parity + guard
                    # cleanliness + halved bytes axes; also lands in
                    # BENCH_bf16.json.
                    "bf16": cmp["bf16"],
                    # Fused edge-pipeline head-to-head
                    # (MEGBA_BENCH_FUSED=1): Pallas kernels vs tiled
                    # XLA — cost parity + zero guards + transient-bytes
                    # delta; also lands in BENCH_fused.json.
                    "fused": cmp["fused"],
                    # Observability-plane overhead (MEGBA_BENCH_OBS=1):
                    # plane off vs metrics+spans on, <= 2% band; also
                    # lands in BENCH_obs.json.
                    "obs": cmp["obs"],
                    # Per-phase wall clocks (compile vs solve, per pass)
                    # so BENCH_*.json artifacts carry phase timings.
                    "phases": {
                        name: {"total_s": round(d["total_s"], 4),
                               "calls": d["calls"]}
                        for name, d in timer.as_dict().items()
                    },
                    # analysis/program_audit summaries (MEGBA_BENCH_AUDIT=1).
                    "program_audit": audit_summaries,
                },
            }
        )
    )

    # Same opt-in sink as solve.flat_solve: one structured SolveReport
    # per bench run when MEGBA_TELEMETRY is set (off: nothing imported).
    telemetry = os.environ.get("MEGBA_TELEMETRY")
    if telemetry:
        from megba_tpu.observability.report import append_report, build_report

        append_report(
            build_report(option, res, timer.as_dict(), {
                "num_cameras": NUM_CAMERAS,
                "num_points": NUM_POINTS,
                "num_edges": int(n_edge),
                "num_edges_padded": int(args[2].shape[-1]),
                "world_size": 1,
                "bench_config": CONFIG,
            }, audit=audit_summaries), telemetry)


if __name__ == "__main__":
    main()
