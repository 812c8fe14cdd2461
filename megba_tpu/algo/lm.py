"""Levenberg-Marquardt trust-region outer loop.

TPU-native replacement for the reference's LMAlgo::solveCUDA
(src/algo/lm_algo.cu:139-223): the same algorithm — damp, solve the Schur
system, test ||dx|| <= eps2(||x|| + eps1), apply, gain ratio rho from the
linearised cost Sum(J dx + e)^2, accept (relinearise, region /= max(1/3,
1-(2 rho - 1)^3), stop when ||g||_inf <= eps1) or reject (region /= v,
v *= 2) — but as a single jitted `lax.while_loop`.

The reference's trickiest machinery disappears in functional form: its
backup/rollback device copies (base_edge.cu:17-44,
schur_LM_linear_system.cu:187-209 — the README.md:15 changelog records a
rollback-correctness bug here) become "carry the old pytree instead of
the new one", and the damping save/restore (recoverDiag) is a pure
function of the undamped blocks.  Each LM iteration runs entirely
on-device: no host-blocking residual-norm or dot reductions
(lm_algo.cu:25-58 syncs the host ~6 times per iteration).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from megba_tpu.analysis.retrace import note_trace, static_key
from megba_tpu.common import (
    ComputeKind,
    PrecondKind,
    PreconditionerKind,
    ProblemOption,
    SolveStatus,
)
from megba_tpu.linear_system.builder import (
    SchurSystem,
    build_schur_system,
    weight_system_inputs,
)
from megba_tpu.observability.emit import (
    emit_verbose_iteration,
    next_verbose_token,
)
from megba_tpu.observability.trace import SolveTrace
from megba_tpu.ops.accum import comp_sum, comp_sum_sq
from megba_tpu.ops.robust import RobustKind, robustify
from megba_tpu.solver.pcg import HI, plain_pcg_solve, schur_pcg_solve

_TINY = 1e-30

# Verbose-line emission moved to observability/emit.py (the single home
# of human-readable solver output); this alias keeps the historical
# import path working.
_next_verbose_token = next_verbose_token


def initial_forcing_eta(eta_min, eta_max, dtype):
    """Eisenstat-Walker start: half the RHS energy removed is plenty for
    the first (least accurate) linearization, never looser than the cap.
    Shared by the BA and PGO loops."""
    return jnp.clip(
        jnp.minimum(eta_max, jnp.asarray(0.5, dtype)), eta_min, None)


def eisenstat_walker_eta(eta_prev, cost_new, cost_prev, rho, accept,
                         eta_min, eta_max, dtype):
    """One Eisenstat-Walker choice-2 forcing update (gamma=0.9, alpha=2).

    Costs are squared residual norms, so the cost ratio IS the norm
    ratio squared.  Safeguarded against over-tightening while the
    previous eta was still loose; loosened when the gain ratio says the
    linear model is trustworthy; tightened on reject (the failed step
    may be the inexact solve's fault, and the shrunken region makes the
    next system cheaper anyway).  Clamped to [eta_min, eta_max].  The
    ONE home of the forcing schedule — the BA and PGO loops both call
    it, so a tuning change can never leave them on different schedules.
    """
    ratio2 = cost_new / jnp.maximum(cost_prev, jnp.asarray(_TINY, dtype))
    eta_ew = 0.9 * ratio2
    safeguard = 0.9 * eta_prev * eta_prev
    eta_ew = jnp.where(safeguard > 0.1,
                       jnp.maximum(eta_ew, safeguard), eta_ew)
    eta_ew = jnp.where(rho > 0.75, 2.0 * eta_ew, eta_ew)
    return jnp.where(accept,
                     jnp.clip(eta_ew, eta_min, eta_max),
                     jnp.maximum(0.25 * eta_prev, eta_min))


def derive_status(*, stopped, accepted, recoveries, fatal):
    """Termination status code (common.SolveStatus), computed on device.

    Shared by the BA and PGO loops and re-derived by the chunked driver
    from whole-solve aggregates.  Priority: a fatal bail-out trumps
    everything; any contained recovery marks the solve `recovered`
    (callers should treat the result as valid but re-validate inputs);
    otherwise the stop flag separates `converged` from budget
    exhaustion, and zero accepted steps downgrade the latter to
    `stalled`.
    """
    status = jnp.where(
        stopped, jnp.int32(SolveStatus.CONVERGED),
        jnp.where(jnp.asarray(accepted) > 0,
                  jnp.int32(SolveStatus.MAX_ITER),
                  jnp.int32(SolveStatus.STALLED)))
    status = jnp.where(jnp.asarray(recoveries) > 0,
                       jnp.int32(SolveStatus.RECOVERED), status)
    return jnp.where(fatal, jnp.int32(SolveStatus.FATAL_NONFINITE), status)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LMResult:
    """Final state + diagnostics of one LM solve."""

    cameras: jax.Array
    points: jax.Array
    cost: jax.Array  # final accepted cost Sum e^2
    initial_cost: jax.Array
    iterations: jax.Array  # LM iterations executed
    accepted: jax.Array  # number of accepted steps
    pcg_iterations: jax.Array  # total PCG iterations across the solve
    region: jax.Array  # final trust region
    v: jax.Array  # final reject back-off factor (resume state)
    stopped: jax.Array  # True when a convergence criterion fired
    # Per-iteration convergence history ([max_iter] arrays masked by
    # `iterations`), recorded on-device inside the while_loop — see
    # observability/trace.py.  None only for results built by legacy
    # constructors that predate the trace.
    trace: Optional[SolveTrace] = None
    # Warm-start resume state: the last ACCEPTED step (the same layout
    # `cameras` uses — feature-major here, edge-major after flat_solve's
    # boundary transpose).  Populated only under
    # SolverOption.warm_start; the chunked/checkpointed drivers thread
    # it back in as `initial_dx` so warm starts survive chunk
    # boundaries.
    dx_cam: Optional[jax.Array] = None
    # Termination semantics (robustness layer): a common.SolveStatus
    # code (int32 scalar, derive_status) and the number of contained
    # fault recoveries the guards performed (0 with guards off).  None
    # only on results built by legacy constructors.
    status: Optional[jax.Array] = None
    recoveries: Optional[jax.Array] = None


# The program's outermost device layer: every op of the LM solve sits
# under it, so what no inner scope claims (the first linearisation's
# cost sums, the loop test, the result) still has a layer name.
@jax.named_scope("megba.lm_solve")
def lm_solve(
    residual_jac_fn: Callable,
    cameras: jax.Array,
    points: jax.Array,
    obs: jax.Array,
    cam_idx: jax.Array,
    pt_idx: jax.Array,
    mask: jax.Array,
    option: ProblemOption,
    sqrt_info: Optional[jax.Array] = None,
    cam_fixed: Optional[jax.Array] = None,
    pt_fixed: Optional[jax.Array] = None,
    axis_name: Optional[str] = None,
    verbose: bool = False,
    cam_sorted: bool = False,
    plans=None,
    initial_region=None,
    initial_v=None,
    verbose_token=None,
    initial_dx=None,
    fault_plan=None,
    cluster_plan=None,
    tile_plan=None,
) -> LMResult:
    """Run the LM loop to convergence.  Jit/shard_map-compatible.

    FEATURE-MAJOR contract (core/fm.py): cameras [cd, Nc], points
    [pd, Np], obs [od, nE], sqrt_info [od*od, nE];
    `residual_jac_fn(cam_rows, pt_rows, obs) -> (r, Jc, Jp)` is the
    row-form engine from ops.residuals.  Edge-axis arrays (obs, cam_idx,
    pt_idx, mask, sqrt_info) may be shard-local when `axis_name` names a
    mesh axis; cameras/points are replicated.

    `initial_region`/`initial_v` override the trust-region start state —
    the resume hook used by utils.checkpoint / solve_checkpointed.
    `initial_dx` ([cd, Nc] rows) seeds the warm-start carry under
    SolverOption.warm_start (the cross-chunk resume hook); ignored
    otherwise.

    `plans` (ops/segtiles.DualPlans) turns on the scatter-free tiled
    path: edge arrays must be in the cam plan's slot order (the lowering
    in solve.py arranges this); internally Jp is carried in PT-slot
    order so both Hessian sides and both coupling products reduce over
    sorted block-aligned segments.

    BATCH-AXIS CONTRACT (serving layer): this loop is `jax.vmap`-safe
    over a leading problem axis on every array operand — the carry is a
    pure pytree of per-problem values (no host scalars, no cross-lane
    reductions when `axis_name is None`), so JAX's while_loop batching
    rule gives per-lane convergence masking for free: the lifted
    predicate keeps the loop running while ANY lane is live, and a lane
    whose `cond` has cleared freezes BITWISE (per-lane select on the
    carry) while its batch-mates keep iterating.  Each lane's
    trajectory is a function of its own slice only; `derive_status`,
    the trace and the final scalars all come back per lane.
    `serving/compile_pool._build_batched_solve` is the production
    consumer; verbose emission is the one vmap-hostile feature (host
    callback), so batched programs run `verbose=False`.

    `cluster_plan` (ops/segtiles.DeviceClusterPlan, or
    DeviceMultiLevelPlan for the MULTILEVEL hierarchy) is the
    host-planned camera-cluster coarse space consumed by the
    TWO_LEVEL/MULTILEVEL preconditioners (solver/precond.py); its
    per-edge `pc_slot` stream is in this call's edge order
    (shard-local when `axis_name` names a mesh axis), everything else
    replicated.  Required when `SolverOption.precond` is TWO_LEVEL or
    MULTILEVEL, ignored otherwise — the flat_solve lowering threads it
    automatically.

    `tile_plan` (ops/segtiles.DeviceCameraTilePlan) arms the 2-D mesh
    matvec (solver/pcg.make_matvec_2d): `axis_name` must then be the
    (EDGE_AXIS, CAM_AXIS) tuple — every existing psum site reduces over
    the tuple (the whole world) unchanged, while the PCG body's matvec
    runs the subgroup-scoped tiled pipeline.  The flat_solve 2-D
    lowering threads it automatically; ignored on the 1-D mesh.

    `fault_plan` (robustness.faults.FaultPlan, edge_nan already in this
    call's edge order) injects deterministic faults at the residual /
    linear-system boundary — the CI harness for the RobustOption guards.
    `option.robust_option.guards` arms on-device fault containment: a
    non-finite step (trial cost, dx, or PCG residual energy) is rolled
    back bitwise (the carry already holds the last accepted state), the
    system is relinearised at the rolled-back point, the trust region is
    divided by `damping_inflation`, and after more than `max_recoveries`
    consecutive failures the loop bails out with
    SolveStatus.FATAL_NONFINITE.  Detection reads only replicated,
    already-psum-reduced scalars, so the sharded program gains no
    collectives; with nothing failing every selected value is bitwise
    identical to the unguarded solve.
    """
    # Retrace sentinel (analysis/retrace.py): note_trace counts only
    # under an active jax trace (eager lm_solve calls are not
    # compilations), so the count equals the number of LM-program
    # compilations for this configuration+signature.
    note_trace("algo.lm_solve", cameras, points, obs, cam_idx, pt_idx,
               static=static_key(residual_jac_fn, option, axis_name,
                                 verbose, cam_sorted))
    if tile_plan is not None and not (
            isinstance(axis_name, (tuple, list)) and len(axis_name) == 2):
        # The 2-D tiled matvec needs the (EDGE_AXIS, CAM_AXIS) tuple to
        # scope its subgroup collectives; on a 1-D mesh (or single
        # device) the plan is documented as ignored — dropping it here
        # keeps that true instead of crashing in make_matvec_2d's
        # axis-tuple unpack.
        tile_plan = None
    num_cameras = cameras.shape[1]
    num_points = points.shape[1]
    algo_opt = option.algo_option
    solver_opt = option.solver_option
    compute_kind = option.compute_kind
    robust_opt = option.robust_option
    guards = robust_opt.guards

    def psum(x):
        if axis_name is None:
            return x
        with jax.named_scope("megba.collective"):
            return jax.lax.psum(x, axis_name)

    robust = option.robust_kind
    robust_delta = option.robust_delta

    def linearize(cams, pts, k=0):
        # named_scope: zero runtime cost, but the residual+Jacobian ops
        # carry a navigable label in trace_profile output
        # (TensorBoard/Perfetto) instead of dissolving into fused soup.
        with jax.named_scope("megba.residual_jacobian"):
            r, Jc, Jp = residual_jac_fn(jnp.take(cams, cam_idx, axis=1),
                                        jnp.take(pts, pt_idx, axis=1), obs)
            r, Jc, Jp = weight_system_inputs(
                r, Jc, Jp, cam_idx, pt_idx, mask, sqrt_info, cam_fixed,
                pt_fixed)
        if fault_plan is not None:
            # Seeded fault (robustness/faults.py): poison AFTER masking
            # so the injection cannot be laundered away by padding, and
            # stamp the call with the LM iteration whose system it
            # produces (the pre-loop linearisation shares stamp 0 with
            # iteration 0's evaluations).
            from megba_tpu.robustness.faults import poison_residuals

            r = poison_residuals(r, fault_plan, k)
        # Costs use compensated f32 sums (ops/accum.py): at BAL-Final
        # scale (~58M terms) a plain f32 sum's O(n*eps) error would flip
        # accept/reject decisions near convergence; the reference gets
        # this accuracy from f64 cuBLAS dots (lm_algo.cu:25-51).
        if robust == RobustKind.NONE:
            wcost = psum(comp_sum_sq(r))
            cost = wcost
        else:
            # IRLS reweighting (ops/robust.py); the system is built from
            # the weighted quantities, the accept test uses Sum rho, the
            # quadratic model is measured from the weighted norm.
            r, Jc, Jp, rho_e = robustify(r, Jc, Jp, robust, robust_delta)
            cost = psum(comp_sum(rho_e))
            wcost = psum(comp_sum_sq(r))
        if plans is not None:
            # Carry Jp in PT-slot order from here on: the point-side
            # build and both coupling products consume it there (one
            # cross permute per linearisation instead of one per use).
            Jp = plans.to_pt(Jp)
        system = build_schur_system(
            r, Jc, Jp, cam_idx, pt_idx, num_cameras, num_points,
            compute_kind=compute_kind, axis_name=axis_name,
            cam_fixed=cam_fixed, pt_fixed=pt_fixed, cam_sorted=cam_sorted,
            plans=plans)
        if fault_plan is not None:
            # Linear-system boundary fault: Schur-block indefiniteness
            # (chosen Hll blocks negated while the window is open).
            from megba_tpu.robustness.faults import poison_system

            system = poison_system(system, fault_plan, k)
        return r, Jc, Jp, system, cost, wcost

    r0, Jc0, Jp0, system0, cost0, wcost0 = linearize(
        cameras, points, jnp.int32(0))

    dtype = cameras.dtype
    forcing = solver_opt.forcing
    warm_start = solver_opt.warm_start
    # eta_k is a NORM-relative forcing term; the PCG threshold is on the
    # residual ENERGY rho, so eta rides squared into the solver.  With
    # forcing on, `tol` is the eta cap (SolverOption docs).
    eta_min_c = jnp.asarray(solver_opt.eta_min, dtype)
    eta_max_c = jnp.asarray(solver_opt.tol, dtype)
    state0 = dict(
        k=jnp.int32(0),
        accepted=jnp.int32(0),
        pcg_total=jnp.int32(0),
        cameras=cameras,
        points=points,
        r=r0,
        Jc=Jc0,
        Jp=Jp0,
        system=system0,
        cost=cost0,
        wcost=wcost0,
        region=jnp.asarray(
            algo_opt.initial_region if initial_region is None else initial_region,
            dtype),
        v=jnp.asarray(2.0 if initial_v is None else initial_v, dtype),
        stop=jnp.bool_(False),
        # Fixed-size on-device history; one .at[k].set per field per
        # iteration, no host traffic (observability/trace.py).
        trace=SolveTrace.empty(algo_opt.max_iter, dtype),
    )
    if forcing:
        state0["eta"] = initial_forcing_eta(eta_min_c, eta_max_c, dtype)
    if warm_start:
        dx0_cam = (jnp.zeros_like(cameras) if initial_dx is None
                   else jnp.asarray(initial_dx, dtype))
        state0["dx0"] = (dx0_cam if option.use_schur
                         else (dx0_cam, jnp.zeros_like(points)))
    if guards:
        # Fault-containment carry: consecutive-failure streak, total
        # contained recoveries, and the fatal bail-out flag.
        state0["fail_streak"] = jnp.int32(0)
        state0["recoveries"] = jnp.int32(0)
        state0["fatal"] = jnp.bool_(False)

    def cond(s):
        return (s["k"] < algo_opt.max_iter) & (~s["stop"])

    if (option.use_schur and cluster_plan is None
            and solver_opt.precond in (PrecondKind.TWO_LEVEL,
                                       PrecondKind.MULTILEVEL)):
        raise ValueError(
            f"SolverOption.precond={solver_opt.precond.name} needs a "
            "camera-cluster plan operand: solve through flat_solve (which "
            "plans + caches it) or pass cluster_plan="
            "ops.segtiles.device_cluster_plan(...) / "
            "device_multilevel_plan(...)")

    pcg_solve = schur_pcg_solve if option.use_schur else plain_pcg_solve

    def body(s):
        # Per-iteration tolerance: the carried eta_k (squared — see
        # above) under forcing, the static option otherwise.  eta_k and
        # the warm-start carry are replicated across shards (derived
        # from psum-reduced costs and the replicated PCG output), so
        # they ride shard_map like the rest of the LM state.
        tol_k = s["eta"] * s["eta"] if forcing else solver_opt.tol
        tol_rel = True if forcing else solver_opt.tol_relative
        with jax.named_scope("megba.pcg"):
            pcg = pcg_solve(
                s["system"], s["Jc"], s["Jp"], cam_idx, pt_idx, s["region"],
                max_iter=solver_opt.max_iter, tol=tol_k,
                refuse_ratio=solver_opt.refuse_ratio,
                tol_relative=tol_rel,
                compute_kind=compute_kind, axis_name=axis_name,
                mixed_precision=option.mixed_precision_pcg,
                bf16=solver_opt.bf16,
                bf16_collectives=solver_opt.bf16_collectives,
                fused_kernels=solver_opt.fused_kernels,
                cam_sorted=cam_sorted,
                preconditioner=solver_opt.preconditioner, plans=plans,
                x0=s["dx0"] if warm_start else None,
                guard=guards,
                max_restarts=robust_opt.pcg_max_restarts if guards else 0,
                precond=solver_opt.precond,
                neumann_order=solver_opt.neumann_order,
                cluster_plan=cluster_plan, cam_fixed=cam_fixed,
                smooth_omega=solver_opt.smooth_omega,
                tile_plan=tile_plan)
        with jax.named_scope("megba.lm_step"):
            return step(s, pcg)

    def step(s, pcg):
        # The LM step past the PCG solve: step norms, the gain ratio's
        # linearised cost (J.dx) and trial cost, accept/reject, and the
        # trust-region update.
        dx_cam, dx_pt = pcg.dx_cam, pcg.dx_pt

        # ||dx|| <= eps2 (||x|| + eps1)  -> converged, don't apply
        # (reference lm_algo.cu:171-179).
        dx_norm = jnp.sqrt(jnp.sum(dx_cam * dx_cam) + jnp.sum(dx_pt * dx_pt))
        x_norm = jnp.sqrt(jnp.sum(s["cameras"] ** 2) + jnp.sum(s["points"] ** 2))
        converged = dx_norm <= algo_opt.epsilon2 * (x_norm + algo_opt.epsilon1)

        cams_new = s["cameras"] + dx_cam
        pts_new = s["points"] + dx_pt

        # Gain-ratio denominator: linearised cost at dx minus old cost
        # (the JdxpF kernel, lm_algo.cu:60-126).  J dx + e, row form:
        od = s["r"].shape[0]
        cd = dx_cam.shape[0]
        pd = dx_pt.shape[0]
        if plans is not None:
            from megba_tpu.ops.segtiles import coupling_expand

            uk = plans.use_kernels
            # Fused (gather + J.dx) on each side; Jp is PT-ordered, so
            # its [od] product rows hop to cam order for the final sum.
            jc_dx = coupling_expand(dx_cam, s["Jc"], plans.cam, cd, uk,
                                    name="coupling_expand_cam_to_edge")
            jp_dx = plans.to_cam(coupling_expand(
                dx_pt, s["Jp"], plans.pt, pd, uk,
                name="coupling_expand_pt_to_edge"))
            jdx = (jc_dx + jp_dx + s["r"]).astype(s["r"].dtype)
        else:
            dxc_e = jnp.take(dx_cam, cam_idx, axis=1)  # [cd, nE]
            dxp_e = jnp.take(dx_pt, pt_idx, axis=1)  # [pd, nE]
            jdx = jnp.stack([
                sum(s["Jc"][o * cd + a] * dxc_e[a] for a in range(cd))
                + sum(s["Jp"][o * pd + b] * dxp_e[b] for b in range(pd))
                + s["r"][o]
                for o in range(od)
            ])
        predicted = psum(comp_sum_sq(jdx))
        # The quadratic model is in the (robust-)weighted residuals; its
        # decrease is measured from the carried weighted norm, while
        # accept uses the true (robustified) cost.  For RobustKind.NONE
        # both equal Sum r^2 and this reduces to the reference formula.
        # The linearised decrease is <= 0 for any useful step; clamp
        # sign-preservingly so an underflowing denominator can't flip
        # rho's sign and collapse the trust region on an accepted step.
        denominator = jnp.minimum(predicted - s["wcost"], -_TINY)

        # Trial-point cost WITHOUT paying for Jacobians or the Hessian
        # build: only the cost outputs of this call are used, so XLA's
        # dead-code elimination prunes the J/system computations from the
        # loop body.  This mirrors the reference's cheap second forward()
        # (residual jets only feed the norm unless the step is accepted,
        # lm_algo.cu:183-189,209-214).
        _, _, _, _, cost_new, wcost_new = linearize(cams_new, pts_new,
                                                    s["k"])
        rho = (cost_new - s["cost"]) / denominator

        # Reference lm_algo.cu breaks BEFORE edges.update() when the
        # step-size test fires — a converged step is never applied.
        accept = (cost_new < s["cost"]) & (~converged)
        recover = jnp.bool_(False)
        if guards:
            # Fault containment.  Every detector input is a replicated
            # scalar that already rode the existing psums (NaN
            # propagates through them), so the sharded program gains no
            # collectives.  `step_bad`: the trial cost, the step, or the
            # PCG's final residual energy left the finite range — the
            # latter catches a poisoned CARRIED system, whose zero-
            # iteration PCG exit would otherwise masquerade as a
            # converged dx = 0.  A `broken` PCG (breakdown-restart
            # budget exhausted — the inner solver declared the operator
            # sick) is a step failure too: its carried system needs the
            # same rollback + relinearisation + damping inflation.
            step_bad = ~(jnp.isfinite(cost_new) & jnp.isfinite(dx_norm)
                         & jnp.isfinite(pcg.rho)) | pcg.broken
            converged = converged & ~step_bad
            # Adoption heals a non-finite CARRIED cost (a fault during
            # the linearisation that produced it — e.g. a poisoned
            # chunk-resume relinearisation): once the step evaluates
            # finite again, accept it unconditionally so the carried
            # cost/wcost rejoin the finite regime.
            adopt = (~jnp.isfinite(s["cost"])) & ~step_bad & ~converged
            accept = (accept & ~step_bad) | adopt
            # Recovery = rollback (the reject path already keeps the
            # last accepted parameters bitwise) + relinearisation at the
            # rolled-back point + damping inflation, counted below.
            recover = step_bad

        # Relinearise on accept — and, with guards armed, on a recovery
        # (at the ROLLED-BACK parameters, healing a poisoned carried
        # r/J/system).  lax.cond: the predicate and the selected
        # parameters are replicated across shards, so all replicas take
        # the same branch and the psums inside stay collective-safe.
        # The reference's reject path likewise skips buildLinearSystem
        # (lm_algo.cu:206-214); round 1 paid a full rebuild per rejected
        # step.
        relin = accept | recover

        def _relinearize(_):
            r_n, Jc_n, Jp_n, system_n, _, _ = linearize(
                jnp.where(accept, cams_new, s["cameras"]),
                jnp.where(accept, pts_new, s["points"]), s["k"])
            return r_n, Jc_n, Jp_n, system_n

        def _keep_old(_):
            return s["r"], s["Jc"], s["Jp"], s["system"]

        with jax.named_scope("megba.lm_accept_reject"):
            r_n, Jc_n, Jp_n, system_n = jax.lax.cond(
                relin, _relinearize, _keep_old, None)

        g_inf = jnp.maximum(jnp.max(jnp.abs(system_n.g_cam)),
                            jnp.max(jnp.abs(system_n.g_pt)))
        region_accept = s["region"] / jnp.maximum(
            jnp.asarray(1.0 / 3.0, dtype), 1.0 - (2.0 * rho - 1.0) ** 3)
        if guards:
            # An adopted (carry-healing) accept has rho = NaN by
            # construction (its denominator ran through the non-finite
            # carried cost); the region must not inherit it.
            region_accept = jnp.where(jnp.isfinite(rho), region_accept,
                                      s["region"])
        stop_accept = g_inf <= algo_opt.epsilon1

        # --- reject branch values ---
        region_reject = s["region"] / s["v"]
        v_reject = s["v"] * 2.0
        if guards:
            # A recovery inflates damping by the configured factor
            # instead of the reject back-off (region ∝ 1/damping), and
            # leaves the back-off factor untouched.
            inflation = jnp.asarray(robust_opt.damping_inflation, dtype)
            region_reject = jnp.where(recover, s["region"] / inflation,
                                      region_reject)
            v_reject = jnp.where(recover, s["v"], v_reject)

        def pick(new, old):
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(accept, a, b), new, old)

        if forcing:
            with jax.named_scope("megba.lm_forcing"):
                eta_next = eisenstat_walker_eta(
                    s["eta"], cost_new, s["cost"], rho, accept,
                    eta_min_c, eta_max_c, dtype)
            if guards:
                # An adopted accept feeds NaN cost ratios through the
                # forcing update; restart the schedule at the cap
                # rather than poisoning every later tolerance.
                eta_next = jnp.where(jnp.isfinite(eta_next), eta_next,
                                     eta_max_c)

        stop = converged | (accept & stop_accept)
        if guards:
            fail_streak = jnp.where(recover, s["fail_streak"] + 1,
                                    jnp.int32(0))
            fatal = fail_streak > robust_opt.max_recoveries
            stop = stop | fatal
        # Robustness trace fields stay None (zero-fill, zero update ops)
        # with guards off; the (enum-coded per-level) precond-fallback
        # count is recorded whenever a preconditioner with a fallback
        # ladder is live — the SCHUR_DIAG block diagonal or any
        # non-JACOBI operator family (solver/precond.py).
        trace_robust = dict(
            precond_fallback=(
                pcg.precond_fallback
                if (solver_opt.preconditioner == PreconditionerKind.SCHUR_DIAG
                    or solver_opt.precond != PrecondKind.JACOBI) else None))
        if guards:
            trace_robust.update(recovery=recover,
                                pcg_breakdown=pcg.breakdowns)
        s_next = dict(
            k=s["k"] + 1,
            accepted=s["accepted"] + jnp.where(accept, 1, 0).astype(jnp.int32),
            pcg_total=s["pcg_total"] + pcg.iterations,
            cameras=pick(cams_new, s["cameras"]),
            points=pick(pts_new, s["points"]),
            # r/Jc/Jp/system already selected by the cond above.
            r=r_n,
            Jc=Jc_n,
            Jp=Jp_n,
            system=system_n,
            cost=jnp.where(accept, cost_new, s["cost"]),
            wcost=jnp.where(accept, wcost_new, s["wcost"]),
            region=jnp.where(accept, region_accept, region_reject),
            v=jnp.where(accept, jnp.asarray(2.0, dtype), v_reject),
            stop=stop,
            # Every recorded value is replicated across shards (costs,
            # g_inf and rho come out of psum-reduced quantities; the
            # trust-region state is carried replicated), so the trace
            # rides shard_map's out_specs=P() unchanged.  `cost` records
            # the TRIAL cost — the same observable the verbose line
            # prints, which the telemetry parity tests pin.
            trace=s["trace"].record(
                s["k"], cost=cost_new, grad_inf_norm=g_inf,
                trust_region=s["region"], rho=rho, accept=accept,
                pcg_iters=pcg.iterations,
                pcg_eta=(s["eta"] if forcing
                         else jnp.asarray(solver_opt.tol, dtype)),
                pcg_r0_ratio=pcg.r0_ratio.astype(dtype),
                **trace_robust),
        )
        if guards:
            s_next["fail_streak"] = fail_streak
            s_next["recoveries"] = s["recoveries"] + recover.astype(jnp.int32)
            s_next["fatal"] = s["fatal"] | fatal
        if forcing:
            s_next["eta"] = eta_next
        if warm_start:
            # Seed the NEXT solve with this iteration's step only when
            # it was accepted; a reject shrinks the trust region (the
            # damped system changes sharply), so the carry is zeroed —
            # bitwise identical to a cold start.
            new_dx = (dx_cam if option.use_schur else (dx_cam, dx_pt))
            s_next["dx0"] = jax.tree_util.tree_map(
                lambda d: jnp.where(accept, d, jnp.zeros_like(d)), new_dx)
        if verbose:
            token = (jnp.int32(0) if verbose_token is None
                     else jnp.asarray(verbose_token, jnp.int32))
            emit_verbose_iteration(token, s["k"], cost_new, accept,
                                   pcg.iterations, axis_name)
        return s_next

    # Under vmap (serving's batched mega-solve) this while_loop batches
    # per-lane: cond lifts to any(pred) and the body's new carry is
    # selected lane-wise against the old one, so a stopped lane costs
    # its share of the batched body's FLOPs but its VALUES are frozen
    # bitwise until the last lane finishes.
    out = jax.lax.while_loop(cond, body, state0)
    dx_final = None
    if warm_start:
        dx_final = out["dx0"] if option.use_schur else out["dx0"][0]
    recoveries = out["recoveries"] if guards else jnp.int32(0)
    fatal = out["fatal"] if guards else jnp.bool_(False)
    status = derive_status(stopped=out["stop"], accepted=out["accepted"],
                           recoveries=recoveries, fatal=fatal)
    return LMResult(
        cameras=out["cameras"],
        points=out["points"],
        cost=out["cost"],
        initial_cost=cost0,
        iterations=out["k"],
        accepted=out["accepted"],
        pcg_iterations=out["pcg_total"],
        region=out["region"],
        v=out["v"],
        stopped=out["stop"],
        trace=out["trace"],
        dx_cam=dx_final,
        status=status,
        recoveries=recoveries,
    )


