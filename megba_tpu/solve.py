"""Flat solve pipeline + one-call BAL convenience.

`flat_solve` is THE lowering pipeline from flat arrays to the jitted
solver — dtype cast, native camera sort, pad/shard, single- or
multi-device dispatch, and jit caching — shared by `BaseProblem.solve`,
`solve_bal`, and the example CLIs so the semantics live in exactly one
place.  The object facade (problem.py) mirrors the reference's g2o-style
API on top; `solve_bal` goes straight from a parsed `BALFile` (or path)
to the solver without building per-edge Python objects.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from megba_tpu.algo.lm import LMResult, lm_solve
from megba_tpu.analysis.retrace import static_key, traced
from megba_tpu.common import (
    PrecondKind,
    ProblemOption,
    strip_observability,
    validate_options,
)
from megba_tpu.core.fm import EDGE_QUANTUM
from megba_tpu.core.types import is_cam_sorted, pad_edges
from megba_tpu.io.bal import BALFile, load_bal
from megba_tpu import observability as _obs
from megba_tpu.observability.emit import next_verbose_token
from megba_tpu.parallel.mesh import (
    distributed_lm_solve,
    get_or_build_program,
    make_mesh,
)
from megba_tpu.utils.backend import warn_if_x64_unavailable
from megba_tpu.utils.timing import PhaseTimer


def default_use_tiled(dtype) -> bool:
    """Whether the scatter-free tiled engine is the default lowering.

    Float32 on TPU backends only: the tiled XLA fallback on CPU is
    slower and fatter than the chunked scatter-add build, and float64
    never rides the kernels.
    """
    if np.dtype(dtype) != np.float32:
        return False
    return jax.default_backend() == "tpu"


def _build_single_solve(residual_jac_fn, option, keys, verbose, cam_sorted):
    """Jitted single-device solve.  The trust-region resume state rides as
    dynamic operands so chunked/checkpointed solves reuse one compilation;
    `plans` (a DualPlans pytree or None) rides as an operand too, so its
    index arrays are solver inputs rather than baked-in constants."""

    def fn(cameras, points, obs, cam_idx, pt_idx, mask, init_region, init_v,
           verbose_token, plans, *extras):
        return lm_solve(
            residual_jac_fn, cameras, points, obs, cam_idx, pt_idx, mask,
            option, verbose=verbose, cam_sorted=cam_sorted,
            plans=plans, initial_region=init_region,
            initial_v=init_v, verbose_token=verbose_token,
            **dict(zip(keys, extras)))

    # Donate the parameter blocks: the result's cameras/points alias the
    # inputs' buffers instead of allocating fresh ones (at Final scale
    # ~53 MB f32 of params per solve call; matters most for chunked /
    # checkpointed drivers that call the program in a loop).  Safe:
    # flat_solve materializes fresh feature-major operands per call and
    # never reads them after the solve.
    # `traced`: retrace sentinel hook (analysis/retrace.py) — counts one
    # trace per compilation of this program; zero cost once compiled.
    return jax.jit(
        traced("solve.single", fn,
               static=static_key(residual_jac_fn, option, keys, verbose,
                                 cam_sorted)),
        donate_argnums=(0, 1))


# Global program cache for long-lived engines (same pitfall and remedy as
# parallel.mesh._cached_sharded_solve).  Per-problem closure engines must
# NOT land here — a global entry would pin the closure (and the prototype
# edge it captures) past its problem's lifetime; they use a caller-owned
# jit_cache instead (see flat_solve).
_cached_single_solve = functools.lru_cache(maxsize=64)(_build_single_solve)


# One host span from entry to return (the program is then enqueued, not
# finished): the profiler's view of every call's host work, around the
# PhaseTimer phases inside it.
@functools.partial(jax.profiler.annotate_function, name="megba.flat_solve")
def flat_solve(
    residual_jac_fn,
    cameras: np.ndarray,
    points: np.ndarray,
    obs: np.ndarray,
    cam_idx: np.ndarray,
    pt_idx: np.ndarray,
    option: ProblemOption,
    sqrt_info: Optional[np.ndarray] = None,
    edge_mask: Optional[np.ndarray] = None,
    cam_fixed: Optional[np.ndarray] = None,
    pt_fixed: Optional[np.ndarray] = None,
    verbose: bool = False,
    use_tiled: Optional[bool] = None,
    initial_region: Optional[float] = None,
    initial_v: Optional[float] = None,
    initial_dx: Optional[np.ndarray] = None,
    fault_plan=None,
    jit_cache: Optional[dict] = None,
    timer: Optional[PhaseTimer] = None,
    elastic_report: Optional[dict] = None,
    triage=None,
    factor=None,
    lower_only: bool = False,
) -> LMResult:
    """Lower flat arrays and run the solve (single- or multi-device).

    PUBLIC BOUNDARY: accepts the conventional edge-major numpy layout
    (cameras [Nc, cd], obs [nE, od], sqrt_info [nE, od, od]) and returns
    an LMResult with edge-major cameras/points.  Internally everything is
    feature-major (core/fm.py) — the transposes happen exactly once,
    here, on host numpy.

    Edges are camera-sorted here (native counting sort) if they are not
    already; `sqrt_info` rides the same permutation.  The edge axis is
    padded to a multiple of world_size * EDGE_QUANTUM (masked-out edges)
    so chunked builds, the Pallas assembly tiles and equal shards all get
    static shapes.

    `edge_mask` ([nE] 0/1, caller's edge order) multiplies into that
    internal padding mask: a 0 edge is EXACTLY the no-op a padded edge
    is (zero residual weight, zero cost contribution) without changing
    the program's static shape — so callers can soft-delete edges, and
    the serving layer's pre-padded buckets (serving/shape_class.py) can
    be replayed through this entry point bit-for-bit (the fleet parity
    tests drive a bucket lane and `flat_solve(..., edge_mask=...)` on
    identical operands).  Purely an operand: toggling it never
    recompiles.  `option.world_size` selects the mesh; jitted programs
    are cached per configuration — globally for long-lived engines, or in
    the caller-owned `jit_cache` dict when the engine is a per-problem
    closure whose lifetime must not exceed its problem's (BaseProblem
    passes its own dict).

    `initial_dx` ([Nc, cd], edge-major like `cameras`) seeds the
    warm-start carry under `SolverOption.warm_start` — the cross-chunk
    resume hook (`LMResult.dx_cam` of the previous chunk); ignored when
    warm starts are off.

    `use_tiled` selects the scatter-free tiled path (ops/segtiles):
    default ON for float32 solves on TPU backends (where it replaces
    every per-edge scatter/gather with block-aligned MXU reductions),
    OFF otherwise (float64 verification and CPU runs keep the chunked
    scatter-add build, whose transient memory is bounded).

    `fault_plan` (robustness.faults.FaultPlan, edge_nan in the CALLER's
    edge order) seeds a deterministic fault into the solve; its edge
    vector rides the same permutation/padding as `obs` so the poison
    lands on the same physical edges in every lowering, and the plan's
    window/offset are dynamic operands (a chunked driver slides the
    fault without recompiling).  Omitted entirely, the program carries
    no injection ops at all.

    `timer` (utils.timing.PhaseTimer, fresh one by default) accumulates
    the host-side phase wall clocks (lowering / sort / plan / program /
    dispatch — "dispatch" includes jit tracing+compilation on the first
    call of a configuration), and inside them the sub-phases plan.key
    (index digest and plan-cache lookup), plan.gather (edges into plan
    order), lowering.transpose (feature-major operands), on one device
    dispatch.upload (index and mask operands), and dispatch.call (the
    jitted call, host operands' transfer included).  With
    telemetry enabled (MEGBA_TELEMETRY=<path> or `option.telemetry`) an
    extra blocking "execute" phase is timed and a SolveReport JSONL line
    is appended; with it disabled the solve stays fully asynchronous and
    the sink module is never even imported.

    `elastic_report` (a dict, robustness.elastic.ElasticMonitor.
    report_block()) attaches the elastic-distribution ledger to this
    call's SolveReport line — context only, like the serving layer's
    `fleet` block; ignored when telemetry is off and never an operand
    of the compiled program.

    `triage` (robustness.triage.TriagePolicy) arms PRE-FLIGHT health
    checks: the problem is structurally and geometrically checked on
    host (pure NumPy, a "triage" PhaseTimer phase) BEFORE any lowering
    or device work.  Under REJECT a degenerate problem raises the
    typed `ProblemRejected` (HealthReport attached) with ZERO device
    dispatch — the timer records a triage phase and no dispatch phase,
    and the retrace sentinel sees no new traces.  Under REPAIR the
    deterministic repairs merge into this call's operands (edge_mask
    multiplies, fixed masks OR, non-finite values sanitised) and the
    repair counters land as `triage_*` PhaseTimer events; under WARN
    the report is attached and the solve is unchanged.  The
    HealthReport rides `SolveReport.health` when telemetry is on.

    `factor` (a registered factor name or `factors.FactorSpec`) routes
    this solve through the factor registry: the arrays are validated
    against the spec's block dims (typed `FactorError` naming the
    offending axis; an unknown name raises typed `UnknownFactorError`
    HERE, before any device work), `residual_jac_fn=None` is resolved
    to the spec's engine via `factors.engine_for` (memoised — one
    config, one engine object, so the jit caches cannot split), robust
    kernels are refused typed on families with `robust_ok=False`, and
    the spec's triage hooks drive the geometric pre-flight checks.
    Without `factor` the call behaves exactly as it always has (the
    caller owns the engine; triage assumes the BAL family).

    `lower_only=True` returns the `jax.stages.Lowered` of the exact
    program this call would have dispatched — same host prep, same
    operands, same jit cache — without executing it.  This is the
    compiled-program auditor's entry point (analysis/program_audit.py):
    what it inspects IS the production program, not a replica.
    """
    factor_spec = None
    if factor is not None:
        from megba_tpu.factors import (
            engine_for,
            get_factor,
            validate_factor_arrays,
        )
        from megba_tpu.factors.registry import FactorError, require_schur
        from megba_tpu.ops.robust import RobustKind

        factor_spec = require_schur(get_factor(factor), "flat_solve")
        validate_factor_arrays(factor_spec, cameras, points, obs,
                               where="flat_solve")
        # Per-factor solver defaults (registry.resolve_refuse_ratio):
        # no built-in Schur family declares one today, but a custom
        # 7-dof-style factor that does gets the same treatment the
        # sim(3) PGO family gets in solve_pgo.
        from megba_tpu.factors.registry import apply_factor_solver_defaults

        option = apply_factor_solver_defaults(factor_spec, option)
        if (option.robust_kind != RobustKind.NONE
                and not factor_spec.robust_ok):
            raise FactorError(
                f"flat_solve: factor {factor_spec.name!r} is not "
                "robust-kernel eligible (robust_ok=False — e.g. a "
                "marginalization prior must not be IRLS-downweighted); "
                "submit with robust_kind=NONE")
        if residual_jac_fn is None:
            residual_jac_fn = engine_for(factor_spec, option.jacobian_mode)
    if residual_jac_fn is None:
        raise ValueError(
            "flat_solve needs residual_jac_fn or a registered factor= "
            "to resolve one from")
    # Resolve the telemetry target here (knob wins over env), then strip
    # the observability knobs (common.OBSERVABILITY_FIELDS): program
    # caches are keyed on `option` and must stay observability-agnostic
    # — turning telemetry or metrics on can never recompile.
    telemetry = option.telemetry or os.environ.get("MEGBA_TELEMETRY") or None
    report_option = option
    option = strip_observability(option)
    timer = PhaseTimer() if timer is None else timer
    # Touch the span recorder up front when MEGBA_TRACE is armed: its
    # first creation installs the PhaseTimer hook, so even a bare
    # flat_solve (no router/batcher to initialise it) records its
    # lowering/plan/dispatch phases as spans.  One env lookup when off.
    _obs.span_recorder()

    health = None
    if triage is not None:
        from megba_tpu.robustness.triage import triage_problem

        # Pre-flight triage BEFORE any lowering: a REJECT propagates
        # `ProblemRejected` out of this phase with nothing traced,
        # compiled or dispatched (the timer ends with a "triage" phase
        # and no "dispatch" phase — the zero-dispatch assertion the
        # tests pin).
        with timer.phase("triage"):
            # Caller-supplied mask/fixed operands are passed through so
            # the checks see the graph the SOLVER will see (a caller-
            # masked edge doesn't count toward degrees, a caller-fixed
            # point can't be "under-constrained").
            outcome = triage_problem(
                cameras, points, obs, cam_idx, pt_idx, triage,
                edge_mask=edge_mask, cam_fixed=cam_fixed,
                pt_fixed=pt_fixed, factor=factor_spec)
        health = outcome.report.to_dict()
        rep = outcome.repair
        if rep is not None and not rep.is_noop:
            for name, n in rep.counters().items():
                if n:
                    timer.count_event(f"triage_{name}", n)
            cameras, points, obs = rep.merged_arrays(cameras, points, obs)
            edge_mask, cam_fixed, pt_fixed = rep.merge_operands(
                edge_mask, cam_fixed, pt_fixed)

    dtype = np.dtype(option.dtype)
    warn_if_x64_unavailable(dtype)
    with timer.phase("lowering"):
        # copy=False: at Final-13682 scale obs alone is ~70MB; don't
        # duplicate arrays that are already the right dtype.
        cameras = np.asarray(cameras).astype(dtype, copy=False)
        points = np.asarray(points).astype(dtype, copy=False)
        obs = np.asarray(obs).astype(dtype, copy=False)
        cam_idx = np.asarray(cam_idx)
        pt_idx = np.asarray(pt_idx)
    n_edges_raw = int(cam_idx.shape[0])
    em = None
    if edge_mask is not None:
        em = np.asarray(edge_mask).astype(dtype, copy=False).reshape(-1)
        if em.shape[0] != n_edges_raw:
            raise ValueError(
                f"edge_mask has {em.shape[0]} entries for a problem "
                f"with {n_edges_raw} edges")
    fault_edge = None
    if fault_plan is not None:
        fault_edge = np.asarray(fault_plan.edge_nan)
        if fault_edge.shape[0] != n_edges_raw:
            raise ValueError(
                f"fault_plan.edge_nan has {fault_edge.shape[0]} entries "
                f"for a problem with {n_edges_raw} edges")

    ws = option.world_size
    mesh2d = bool(ws > 1 and option.use_schur
                  and option.solver_option.mesh_2d)
    fused = bool(option.use_schur and option.solver_option.fused_kernels)
    if mesh2d:
        if use_tiled:
            raise ValueError(
                "mesh_2d does not compose with the Pallas tiled plans "
                "(use_tiled=True); the 2-D lowering has its own "
                "camera-tile plan — pass use_tiled=False/None")
        use_tiled = False
    if fused and ws > 1 and not mesh2d:
        raise ValueError(
            "SolverOption.fused_kernels is implemented for the "
            "single-device tiled lowering and the 2-D mesh ring step; "
            "the 1-D multi-device lowerings keep the segtiles/XLA "
            "paths — pass fused_kernels=False, or mesh_2d=True for a "
            "fused distributed solve")
    if option.use_schur and option.solver_option.bf16 and not fused:
        # The bf16 MXU pipeline rides the XLA lowering: the tiled
        # coupling kernels (ops/segtiles) have no bf16 operand path, so
        # the default-tiled TPU lane silently measuring f32 kernels
        # would defeat the rung.  Explicit use_tiled=True is refused;
        # the default resolves to the chunked build.  The FUSED
        # edge-pipeline kernels (SolverOption.fused_kernels) DO carry
        # bf16 operand tiles, so the refusal is lifted when they are
        # on (the fused-path tiled+bf16 combination is the legal one).
        if use_tiled:
            raise ValueError(
                "SolverOption.bf16 does not compose with the tiled "
                "plans (use_tiled=True); the bf16 coupling products "
                "ride the XLA lowering — pass use_tiled=False/None, "
                "or enable SolverOption(fused_kernels=True), whose "
                "fused edge-pipeline kernels take bf16 operand tiles")
        use_tiled = False
    if fused and not mesh2d:
        # The fused kernels replace the tiled coupling pipeline; the
        # non-tiled XLA lowering has no edge plan for them to fuse.
        if use_tiled is not None and not use_tiled:
            raise ValueError(
                "SolverOption.fused_kernels needs the tiled edge plans "
                "(they carry the fused bucket ordering); pass "
                "use_tiled=True/None, or fused_kernels=False for the "
                "plain XLA lowering")
        use_tiled = True
    if use_tiled is None:
        use_tiled = default_use_tiled(dtype)

    from megba_tpu.common import EdgeOrder

    if (option.solver_option.edge_order == EdgeOrder.COOBS and not mesh2d):
        # PI-BA co-observation ordering for the 1-D paths (the 2-D plan
        # orders its own streams co-observation-first regardless): a
        # pure host pre-permutation of the caller's edge set — the later
        # camera sorts are stable, so the point-minor order survives
        # into every lowering.  Results agree at solver tolerance (sums
        # reorder), never bitwise; NATURAL keeps every existing program
        # byte-identical.
        from megba_tpu.ops.segtiles import coobservation_edge_order

        with timer.phase("sort"):
            operm = coobservation_edge_order(cam_idx, pt_idx)
            cam_idx, pt_idx, obs = cam_idx[operm], pt_idx[operm], obs[operm]
            if sqrt_info is not None:
                sqrt_info = np.asarray(sqrt_info)[operm]
            if em is not None:
                em = em[operm]
            if fault_edge is not None:
                fault_edge = fault_edge[operm]

    plans = None
    tile_plan_j = None
    tiles_info = None  # per-solve tile/reuse metrics (SolveReport.tiles)
    if mesh2d:
        # 2-D camera x edge lowering: the cached camera-tile plan
        # assigns every edge to its camera tile's column, orders each
        # column co-observation-first, and lays the padded stream out
        # in the P((EDGE_AXIS, CAM_AXIS)) device-block order; the
        # device half rides the program as a pytree operand exactly
        # like the cluster plans, so toggling mesh_2d never bakes
        # indices into a compiled program.
        from megba_tpu.ops.segtiles import (
            cached_camera_tile_plan,
            plan_cache_evictions,
        )
        from megba_tpu.parallel.mesh import factor_mesh_2d

        n_shards, n_blocks = factor_mesh_2d(
            ws, option.solver_option.cam_blocks)
        with timer.phase("plan"):
            evict0 = plan_cache_evictions()
            (tplan, tile_plan_j), plan_hit = cached_camera_tile_plan(
                cam_idx, pt_idx, cameras.shape[0], points.shape[0],
                n_shards, n_blocks, timer=timer)
            if plan_hit:
                timer.count_event("plan_cache_hit")
            evicted = plan_cache_evictions() - evict0
            if evicted:
                timer.count_event("plan_cache_evict", evicted)
            with timer.phase("plan.gather"):
                perm, pmask = tplan.perm, tplan.mask
                obs = obs[perm] * pmask[:, None].astype(dtype)
                cam_idx = tplan.cam_idx
                pt_idx = tplan.pt_idx
                mask = pmask.astype(dtype)
                if em is not None:
                    # Padding slots repeat caller edge 0 under pmask 0, so
                    # the soft-delete mask multiplies in exactly.
                    mask = mask * em[perm]
                if sqrt_info is not None:
                    sqrt_info = np.asarray(sqrt_info)[perm]
                if fault_edge is not None:
                    from megba_tpu.robustness.faults import lower_edge_vector

                    fault_edge = lower_edge_vector(fault_edge, perm, pmask)
            n_padded = obs.shape[0]
            tiles_info = {
                "plan": "mesh2d",
                "cam_blocks": tplan.cam_blocks,
                "tile_cams": tplan.tile_cams,
                "shard_points": tplan.shard_points,
                **{k: tplan.reuse[k] for k in sorted(tplan.reuse)},
            }
    elif use_tiled and ws > 1:
        # Sharded tiled lowering: contiguous per-shard edge chunks, each
        # with its own dual plans; the concatenated per-shard slot
        # streams form the edge axis (equal shard sizes by construction).
        from megba_tpu.ops.segtiles import (
            cached_sharded_dual_plans,
            plan_cache_evictions,
        )

        with timer.phase("plan"):
            evict0 = plan_cache_evictions()
            (perms, masks, cam_segs, plans), plan_hit = (
                cached_sharded_dual_plans(
                    cam_idx, pt_idx, cameras.shape[0], points.shape[0], ws,
                    timer=timer))
            if plan_hit:
                timer.count_event("plan_cache_hit")
            evicted = plan_cache_evictions() - evict0
            if evicted:
                timer.count_event("plan_cache_evict", evicted)
            with timer.phase("plan.gather"):
                obs = np.concatenate([
                    obs[perms[k]] * masks[k][:, None].astype(dtype)
                    for k in range(ws)])
                # cam_segs keeps each shard's cam stream non-decreasing
                # (padding carries the block's running-max camera) so the
                # sorted-scatter promise downstream stays honest; masked
                # slots contribute zeros.
                cam_idx_sh = cam_segs.reshape(-1).astype(np.int32)
                pt_idx_sh = np.concatenate([
                    np.where(masks[k] > 0, pt_idx[perms[k]], 0)
                    for k in range(ws)]).astype(np.int32)
                if sqrt_info is not None:
                    sqrt_info = np.concatenate(
                        [np.asarray(sqrt_info)[perms[k]] for k in range(ws)])
                if fault_edge is not None:
                    from megba_tpu.robustness.faults import lower_edge_vector

                    fault_edge = np.concatenate([
                        lower_edge_vector(fault_edge, perms[k], masks[k])
                        for k in range(ws)])
                cam_idx, pt_idx = cam_idx_sh, pt_idx_sh
                if em is not None:
                    # Each shard's slot stream permutes the caller's edge
                    # order; the soft-delete mask rides the same perms and
                    # lands multiplicatively on the shard padding mask.
                    mask = np.concatenate([
                        masks[k].astype(dtype) * em[perms[k]]
                        for k in range(ws)])
                else:
                    mask = masks.reshape(-1).astype(dtype)
            n_padded = obs.shape[0]
            if telemetry:
                # Per-shard counters of the sharded plans (SolveReport.
                # tiles): each shard's real edges in its slots (every
                # shard has the same slot count), and the one-hot windows,
                # which every shard runs at the widest shard's width.
                edges = [int(m.sum()) for m in masks]
                slots = int(masks.shape[1])
                pt = plans.pt
                tiles_info = {
                    "plan": "tiled_sharded",
                    "shard_edges": edges,
                    "shard_slots": slots,
                    "shard_occupancy": [round(e / slots, 4) for e in edges],
                    "window_cam": plans.cam.window,
                    "window_pt": pt.window,
                    "window_share_pt": pt.window / pt.block,
                }
    elif use_tiled:
        # Tiled lowering: the cam plan's slot order IS the edge axis from
        # here on (it subsumes the camera sort and quantum padding).
        from megba_tpu.ops.segtiles import (
            cached_dual_plans,
            plan_cache_evictions,
        )

        with timer.phase("plan"):
            evict0 = plan_cache_evictions()
            (plan_c, plans), plan_hit = cached_dual_plans(
                cam_idx, pt_idx, cameras.shape[0], points.shape[0],
                timer=timer)
            if plan_hit:
                timer.count_event("plan_cache_hit")
            evicted = plan_cache_evictions() - evict0
            if evicted:
                timer.count_event("plan_cache_evict", evicted)
            with timer.phase("plan.gather"):
                perm, pmask = plan_c.perm, plan_c.mask
                obs = obs[perm] * pmask[:, None].astype(dtype)
                cam_idx = plan_c.seg
                pt_idx = np.where(pmask > 0, pt_idx[perm], 0).astype(np.int32)
                mask = pmask.astype(dtype)
                if em is not None:
                    mask = mask * em[perm]
                if sqrt_info is not None:
                    sqrt_info = np.asarray(sqrt_info)[perm]
                if fault_edge is not None:
                    from megba_tpu.robustness.faults import lower_edge_vector

                    fault_edge = lower_edge_vector(fault_edge, perm, pmask)
            n_padded = obs.shape[0]
            if fused:
                # Fused edge-pipeline bucket plans, one per matvec
                # direction, built over the SAME cam-slot stream the
                # dual plans just produced (pmask marks its padding;
                # any soft-delete weights live in the coupling rows,
                # not the plan).  Host numpy, attached as optional
                # pytree fields — with fused_kernels off these stay
                # None and every program lowers byte-identically.
                import dataclasses as _dc

                from megba_tpu.ops.fused import build_fused_dual_plans

                fp_tp, fp_tc, dfp_tp, dfp_tc = build_fused_dual_plans(
                    cam_idx, pt_idx, pmask,
                    cameras.shape[0], points.shape[0])
                plans = _dc.replace(
                    plans, fused_to_pt=dfp_tp, fused_to_cam=dfp_tc)
            if telemetry:
                # Streaming-reuse + occupancy metrics of the planned
                # stream (SolveReport.tiles, which only the report
                # reads): what the tile ordering — and the fused
                # kernels, when on — actually have to work with.
                from megba_tpu.ops.fused import fused_plan_summary
                from megba_tpu.ops.segtiles import edge_stream_reuse

                tiles_info = {
                    "plan": "tiled_1d",
                    "occupancy": round(
                        plan_c.n_edges / max(1, plan_c.n_slots), 4),
                    **edge_stream_reuse(cam_idx, pt_idx, plan_c.block,
                                        plans.pt.block, mask=pmask),
                    # One-hot width of the segtiles kernels per side:
                    # the block unless the plan's tiles span less.
                    "window_cam": plans.cam.window,
                    "window_pt": plans.pt.window,
                    "window_share_pt": plans.pt.window / plans.pt.block,
                }
                if fused:
                    tiles_info["fused_to_pt"] = fused_plan_summary(fp_tp)
                    tiles_info["fused_to_cam"] = fused_plan_summary(fp_tc)
    else:
        with timer.phase("sort"):
            if not is_cam_sorted(cam_idx):
                from megba_tpu.native import sort_edges_by_camera

                perm = sort_edges_by_camera(cam_idx, cameras.shape[0])
                cam_idx, pt_idx, obs = cam_idx[perm], pt_idx[perm], obs[perm]
                if sqrt_info is not None:
                    sqrt_info = np.asarray(sqrt_info)[perm]
                if fault_edge is not None:
                    fault_edge = fault_edge[perm]
                if em is not None:
                    em = em[perm]

            # Pad the edge axis: every shard must be a multiple of
            # EDGE_QUANTUM so chunk slices and shards are static-shape.
            obs, cam_idx, pt_idx, mask = pad_edges(
                obs, cam_idx, pt_idx, ws * EDGE_QUANTUM, dtype=dtype)
            n_padded = obs.shape[0]
            if em is not None:
                # 1*em on the real region, 0 on the pad region — for an
                # already-quantum-sized input this IS the caller's mask
                # bit-for-bit (1.0 * {0.0, 1.0} is exact).
                mask = mask * np.concatenate(
                    [em, np.zeros(n_padded - em.shape[0], dtype)])
            if fault_edge is not None:
                from megba_tpu.robustness.faults import lower_edge_vector

                fault_edge = lower_edge_vector(fault_edge,
                                               n_padded=n_padded)
    # Two-level preconditioner coarse space: the camera-cluster plan is
    # pure graph structure over the FINAL (post-sort/-plan, padded) edge
    # stream, planned on host once and cached behind the same
    # content-fingerprint LRU as the tile plans; it rides the program as
    # an ordinary pytree operand (like `plans`), so toggling precond
    # kinds never bakes indices into the compiled program.
    cluster_plan_j = None
    if (option.use_schur
            and option.solver_option.precond == PrecondKind.TWO_LEVEL):
        from megba_tpu.ops.segtiles import cached_cluster_plan

        with timer.phase("plan"):
            (_, cluster_plan_j), cl_hit = cached_cluster_plan(
                np.asarray(cam_idx), np.asarray(pt_idx),
                int(cameras.shape[0]), int(points.shape[0]),
                option.solver_option.coarse_clusters,
                mask=np.asarray(mask), world_size=ws,
                smooth_omega=option.solver_option.smooth_omega)
            if cl_hit:
                timer.count_event("cluster_plan_cache_hit")
    elif (option.use_schur
          and option.solver_option.precond == PrecondKind.MULTILEVEL):
        # Recursive hierarchy: same contract as the two-level plan (one
        # host plan over the final padded edge stream, cached), plus the
        # per-level aggregation chain; EVERY aggregation knob is in the
        # cache fingerprint so a SolverOption flip can never serve a
        # stale hierarchy.
        from megba_tpu.ops.segtiles import cached_multilevel_plan

        with timer.phase("plan"):
            (_, cluster_plan_j), cl_hit = cached_multilevel_plan(
                np.asarray(cam_idx), np.asarray(pt_idx),
                int(cameras.shape[0]), int(points.shape[0]),
                option.solver_option.coarse_clusters,
                mask=np.asarray(mask), world_size=ws,
                coarsen_factor=option.solver_option.coarsen_factor,
                max_levels=option.solver_option.max_levels,
                smooth_omega=option.solver_option.smooth_omega)
            if cl_hit:
                timer.count_event("cluster_plan_cache_hit")

    if sqrt_info is not None:
        si = np.asarray(sqrt_info).astype(dtype, copy=False)
        if si.shape[0] != n_padded:
            pad = n_padded - si.shape[0]
            eye = np.broadcast_to(
                np.eye(si.shape[1], dtype=dtype), (pad,) + si.shape[1:])
            si = np.concatenate([si, eye])
        # [nE, od, od] -> feature-major rows [od*od, nE]
        sqrt_info_j = np.ascontiguousarray(si.reshape(n_padded, -1).T)
    else:
        sqrt_info_j = None
    cam_fixed_j = None if cam_fixed is None else np.asarray(cam_fixed)
    pt_fixed_j = None if pt_fixed is None else np.asarray(pt_fixed)
    # Warm-start resume state rides the same optional-operand mechanism
    # as sqrt_info/fixed masks; feature-major like cameras.  Dropped when
    # warm starts are off so the program cache keys stay stable.
    initial_dx_j = None
    if initial_dx is not None and option.solver_option.warm_start:
        initial_dx_j = np.ascontiguousarray(
            np.asarray(initial_dx).astype(dtype, copy=False).T)
    fault_j = None
    if fault_plan is not None:
        fault_j = dataclasses.replace(
            fault_plan,
            edge_nan=np.ascontiguousarray(fault_edge),
            point_crush=np.asarray(fault_plan.point_crush),
            window=np.asarray(fault_plan.window, np.int32),
            offset=np.asarray(fault_plan.offset, np.int32))

    # Feature-major boundary transposes (host numpy, once per solve).
    # Stay on HOST here: the jitted program uploads each operand exactly
    # once on call — and the multi-process path builds global arrays
    # straight from host memory (a premature jnp.asarray would cost a
    # device->host->device round trip per operand there).
    with timer.phase("lowering"), timer.phase("lowering.transpose"):
        cameras_fm = np.ascontiguousarray(cameras.T)
        points_fm = np.ascontiguousarray(points.T)
        obs_fm = np.ascontiguousarray(obs.T)

    problem_shape = {
        "num_cameras": int(cameras.shape[0]),
        "num_points": int(points.shape[0]),
        "num_edges": n_edges_raw,
        "num_edges_padded": int(n_padded),
        "world_size": ws,
    }
    if mesh2d:
        problem_shape["mesh"] = f"{n_shards}x{n_blocks}"

    if ws > 1:
        if mesh2d:
            from megba_tpu.parallel.mesh import make_mesh_2d

            mesh = make_mesh_2d(n_shards, n_blocks)
        else:
            mesh = make_mesh(ws)
        with timer.phase("dispatch"):
            result = distributed_lm_solve(
                residual_jac_fn, cameras_fm, points_fm,
                obs_fm, np.asarray(cam_idx), np.asarray(pt_idx),
                np.asarray(mask), option, mesh,
                sqrt_info=sqrt_info_j, cam_fixed=cam_fixed_j,
                pt_fixed=pt_fixed_j,
                verbose=verbose, cam_sorted=True, plans=plans,
                initial_region=initial_region, initial_v=initial_v,
                initial_dx=initial_dx_j, fault_plan=fault_j,
                cluster_plan=cluster_plan_j, tile_plan=tile_plan_j,
                jit_cache=jit_cache, donate=True, lower_only=lower_only,
                timer=timer)
        if lower_only:
            return result
        result = _result_to_edge_major(result)
        _maybe_emit_report(telemetry, report_option, result, timer,
                           problem_shape, elastic=elastic_report,
                           health=health, tiles=tiles_info)
        return result

    optional = [("sqrt_info", sqrt_info_j), ("cam_fixed", cam_fixed_j),
                ("pt_fixed", pt_fixed_j), ("initial_dx", initial_dx_j),
                ("fault_plan", fault_j), ("cluster_plan", cluster_plan_j)]
    keys = tuple(k for k, v in optional if v is not None)
    extras = [v for _, v in optional if v is not None]
    with timer.phase("program"):
        jitted = get_or_build_program(
            jit_cache, _cached_single_solve, _build_single_solve,
            residual_jac_fn, option, keys, verbose, True)
    ir = option.algo_option.initial_region if initial_region is None else initial_region
    iv = 2.0 if initial_v is None else initial_v

    # ONE operand list for both .lower() and the dispatch: the audited
    # program must be byte-for-byte the dispatched one.  Built inside
    # the dispatch phase so the jnp.asarray index/mask uploads stay part
    # of the timed dispatch cost, as they always were (telemetry phase
    # breakdowns must stay comparable across artifacts).
    with timer.phase("dispatch"):
        with timer.phase("dispatch.upload"):
            call_args = (
                cameras_fm, points_fm, obs_fm,
                jnp.asarray(cam_idx), jnp.asarray(pt_idx),
                jnp.asarray(mask), jnp.asarray(ir, dtype),
                jnp.asarray(iv, dtype),
                jnp.asarray(next_verbose_token(), jnp.int32), plans, *extras)
        if lower_only:
            return jitted.lower(*call_args)
        with timer.phase("dispatch.call"):
            result = jitted(*call_args)
    result = _result_to_edge_major(result)
    _maybe_emit_report(telemetry, report_option, result, timer,
                       problem_shape, elastic=elastic_report,
                       health=health, tiles=tiles_info)
    return result


def _maybe_emit_report(telemetry, option, result, timer, problem,
                       elastic=None, health=None, tiles=None) -> None:
    """Append a SolveReport JSONL line when telemetry is on, and feed
    the per-solve metrics observables when the metrics plane is armed;
    no-op (no sink import, no device sync) when both are off."""
    registry = _obs.metrics_registry(getattr(option, "metrics", False))
    if not telemetry and registry is None:
        return
    # The report wants final scalars + the trace anyway, so the blocking
    # "execute" phase is honest accounting, not added overhead.  (The
    # metrics-only path pays the same sync: iteration counts live on
    # device.  Neither path adds a dispatch — the program is untouched.)
    with timer.phase("execute") as ph:
        ph.sync(result)
    if jax.process_index() != 0:
        return  # one report line per solve, not one per host
    if registry is not None:
        from megba_tpu.observability import metrics as _metrics
        from megba_tpu.common import status_name as _sn

        status = getattr(result, "status", None)
        registry.histogram(
            "megba_solve_lm_iterations",
            "LM iterations per solved problem",
            buckets=_metrics.ITER_BUCKETS).observe(
                int(result.iterations), bucket="unbatched", factor="-")
        registry.histogram(
            "megba_solve_pcg_iterations",
            "Total PCG iterations per solved problem",
            buckets=_metrics.ITER_BUCKETS).observe(
                int(result.pcg_iterations), bucket="unbatched", factor="-")
        if status is not None:
            registry.counter(
                "megba_solve_status_total",
                "Solve outcomes by SolveStatus name").inc(
                    1, status=_sn(status), bucket="unbatched")
    if not telemetry:
        return
    trace = getattr(result, "trace", None)
    if trace is not None:
        # Surface the robustness counters as PhaseTimer events (the
        # report is already paying the device sync): how many contained
        # recoveries the guards performed, and the per-LEVEL
        # preconditioner fallback counts — the trace carries one
        # enum-coded int32 per iteration (solver/precond.py
        # encode/decode: low bits = SCHUR_DIAG blocks fallen back to
        # Hpp, high bits = two-level coarse factors degraded to
        # block-Jacobi), decoded so a coarse-level degrade is visible
        # as its own event, not laundered into a block count.  (The
        # report module is imported below anyway — telemetry is on.)
        from megba_tpu.observability.report import _decode_fallback_totals

        iters = int(result.iterations)
        level = _decode_fallback_totals(trace, iters) or {}
        if level.get("block"):
            timer.count_event("precond_fallback", level["block"])
        if level.get("coarse"):
            timer.count_event("precond_fallback_coarse", level["coarse"])
        # Multilevel hierarchies: one event per DEGRADED coarse level
        # (bit l-1 of the code's high half), so a mid-hierarchy
        # truncation is visible as its own telemetry stream.
        for li, n in enumerate(level.get("coarse_levels") or []):
            if n:
                timer.count_event(f"precond_fallback_coarse_l{li + 1}", n)
        recov = getattr(result, "recoveries", None)
        if recov is not None and int(recov):
            timer.count_event("fault_recovery", int(recov))
    from megba_tpu.observability.report import append_report, build_report

    append_report(
        build_report(option, result, timer.as_dict(), problem,
                     elastic=elastic, health=health, tiles=tiles),
        telemetry)


def _result_to_edge_major(result: LMResult) -> LMResult:
    """Transpose the solved parameters back to the public [N, d] layout."""
    import dataclasses

    return dataclasses.replace(
        result,
        cameras=jnp.swapaxes(result.cameras, 0, 1),
        points=jnp.swapaxes(result.points, 0, 1),
        dx_cam=(None if result.dx_cam is None
                else jnp.swapaxes(result.dx_cam, 0, 1)))


def solve_bal(
    bal: Union[BALFile, str, os.PathLike],
    option: Optional[ProblemOption] = None,
    verbose: bool = False,
) -> Tuple[BALFile, LMResult]:
    """Solve a BAL problem end to end.

    Accepts a parsed `BALFile` or a path (.txt/.bz2).  Uses
    `option.jacobian_mode`, `option.compute_kind`, `option.world_size`,
    dtype, robust/mixed-precision settings.  Returns (solved BALFile with
    updated cameras/points and the ORIGINAL edge order, LMResult).
    """
    option = option or ProblemOption()
    validate_options(option)
    if not isinstance(bal, BALFile):
        bal = load_bal(bal, dtype=option.dtype)

    if verbose:
        from megba_tpu.native import degree_stats
        from megba_tpu.observability.emit import emit_problem_stats

        _, _, (max_cd, max_pd, nnz) = degree_stats(
            bal.cam_idx, bal.pt_idx, bal.num_cameras, bal.num_points)
        # Shared emitter (observability/emit.py): the same formatter the
        # telemetry pipeline documents, so stdout and reports can't drift.
        emit_problem_stats(bal.num_cameras, bal.num_points,
                           bal.num_observations, max_cd, max_pd, nnz)

    # Registry-dispatched: factor="bal" resolves the IDENTICAL engine
    # object the historical make_residual_jacobian_fn(mode=...) default
    # returned (factors/engine.py canonicalisation), so this refactor
    # is program-cache- and bitwise-neutral.
    result = flat_solve(
        None, bal.cameras, bal.points, bal.obs, bal.cam_idx, bal.pt_idx,
        option, verbose=verbose, factor="bal")

    solved = BALFile(
        cameras=np.asarray(result.cameras, dtype=np.float64),
        points=np.asarray(result.points, dtype=np.float64),
        obs=bal.obs,  # original order/values
        cam_idx=bal.cam_idx,
        pt_idx=bal.pt_idx,
    )
    return solved, result
