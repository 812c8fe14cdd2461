"""Distributed Schur-complement preconditioned conjugate gradients
(feature-major).

TPU-native replacement for the reference's SchurPCGSolver /
ImplicitSchurPCGSolver (src/solver/schur_pcg_solver.cu:598-639,
src/solver/implicit_schur_pcg_solver.cu): the same pipeline —

  1. invert the damped Hll blocks (cublasGmatinvBatched there, row-form
     closed-form adjugates here — core/fm.py);
  2. reduced RHS v = g_cam - Hpl Hll^-1 g_pt     [1 psum]
  3. PCG on S x = v with S = Hpp - Hpl Hll^-1 Hlp, block-Jacobi
     preconditioner M^-1 = Hpp^-1                 [2 psums / iteration]
  4. back-substitute dx_pt = Hll^-1 (g_pt - Hlp x) [1 psum]

— but as one jitted `lax.while_loop` with everything on-device: the
reference's per-iteration host-blocking dot products
(schur_pcg_solver.cu:277-287,368-384) become plain on-device reductions
over replicated vectors, and its NCCL allreduces of the coupling products
(schur_pcg_solver.cu:211-242,325-357,502-509,568-575) become
`jax.lax.psum` of the scatter-add outputs.

The Hpl/Hlp products never materialise a sparse matrix: EXPLICIT mode
uses the per-edge W rows (gather -> row products -> scatter-add),
IMPLICIT mode recomputes Jc^T (Jp x) from the stored Jacobian rows
(matrix-free, the reference's implicitEMulx / implicitETMulx,
implicit_schur_pcg_solver.cu:20-90).  All per-edge work is row-wise VPU
math over 128-edge lanes (see core/fm.py for the layout rationale);
there is no cuSPARSE analog to port.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

# The all-gather whose result is typed invariant over the gathered axis
# (jax.lax.all_gather's is typed varying although every member holds
# the same value), so the 2-D matvec's camera vectors stay replicated
# under shard_map's varying-axes check.  Same HLO all-gather; jax 0.9
# keeps it internal, and an API move fails this import loudly.
from jax._src.lax.parallel import all_gather_invariant

from megba_tpu.analysis.retrace import note_trace, static_key
from megba_tpu.common import ComputeKind, PrecondKind, PreconditionerKind
from megba_tpu.core.fm import (
    block_inv_fm,
    block_matvec_fm,
    damp_rows_fm,
    gather_fm,
    segsum_fm,
)
from megba_tpu.linear_system.builder import SchurSystem, damp_blocks
from megba_tpu.ops.accum import comp_dot
from megba_tpu.ops.segtiles import DualPlans, seg_expand, seg_reduce
# The preconditioner subsystem (solver/precond.py) owns the operator
# family; block_inv / cam_block_matvec / _schur_diag_precond are
# re-exported here for the historical import path.
from megba_tpu.solver.precond import (  # noqa: F401  (re-exports)
    _schur_diag_precond,
    block_inv,
    cam_block_matvec,
    make_schur_preconditioner,
)

HI = jax.lax.Precision.HIGHEST

# Absolute floor for the relative PCG threshold (guards rho0 == 0).
_TINY_RHO = 1e-30

# Relative-energy floor for the bf16 MXU pipeline's inner solve: the
# bf16-operand matvec resolves residual NORMS down to ~several
# eps_bf16 (eps_bf16 = 2⁻⁸ ≈ 3.9e-3, conditioning-amplified); energies
# are norms squared, so relative thresholds below ~1e-3 (norm ~3e-2 —
# still a conventional inexact-Newton forcing term) ask the inner
# solve for digits the operator does not carry and spin it at its
# noise floor until the breakdown guard fires.  Applied only under
# tol_relative (schur_pcg_solve); measured on small noised BA systems
# 1e-4 still stagnates, 1e-3 runs guard-clean.
_BF16_TOL_FLOOR = 1e-3


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PCGResult:
    """Solve output: the Schur update and diagnostics (feature-major)."""

    dx_cam: jax.Array  # [cd, Nc]
    dx_pt: jax.Array  # [pd, Np]
    iterations: jax.Array  # scalar int32
    rho: jax.Array  # final residual-energy <r, M^-1 r>
    # |<r0, M^-1 r0>| / |<b, M^-1 b>|: how much of the RHS energy the
    # warm start already removed (1.0 for a cold start).  The LM loop
    # records it per iteration (observability/trace.py).
    r0_ratio: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.float32(1.0))
    # Robustness diagnostics (RobustOption.guards): in-loop cold
    # restarts the breakdown guard performed, whether the solve exited
    # flagged (restart budget exhausted), and how many Schur-diagonal
    # preconditioner blocks fell back to the Hpp preconditioner after a
    # Cholesky NaN (0 for the HPP preconditioner).
    breakdowns: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    broken: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.bool_(False))
    precond_fallback: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    # Compensated elementwise multiply + two-sum tree (ops/accum.py):
    # stays on the VPU, f64-class accuracy in f32 — alpha/beta from
    # noisy dots stall CG convergence at BAL-Final scale.  Vectors are
    # replicated across shards, so no psum is needed — unlike the
    # reference's per-rank sliced dots + host sum
    # (schur_pcg_solver.cu:277-287).
    return comp_dot(a, b)


# Per-edge coupling contractions shared by the 1-D hlp/hpl closures and
# the 2-D tiled matvec (make_matvec_2d steps 1 and 4): ONE copy of each
# W / Jc-Jp block-row layout (EXPLICIT rows W[a*pd+b]; Jacobian-mode
# rows Jc[o*cd+a], Jp[o*pd+b]) so a layout change cannot silently land
# on only one path.  The three precision hooks come from
# `_edge_precision`: `up` is applied to every stored row before it is
# multiplied (the mixed-precision upcast), `acc` to every per-edge
# PRODUCT before it enters a sum (the bf16 pipeline's f32-accumulation
# upcast), `vec` to the gathered Krylov-vector rows / intermediates
# (the bf16 pipeline's operand downcast).  In f32 and mixed modes `acc`
# and `vec` are identities that emit no ops, so every pre-bf16 program
# lowers byte-identically.


def _ident(x):
    return x


def _edge_precision(mixed_precision: bool, bf16_ops: bool):
    """(up, vec, acc) casts for the per-edge coupling products.

    f32 (default):  multiply f32 x f32, accumulate f32 — all identity.
    mixed:          stored rows are bf16; upcast BEFORE multiplying
                    (f32 x f32 products — the PR-era mixed rung).
    bf16 pipeline:  stored rows stay bf16, the gathered vector rows are
                    downcast to bf16 (`vec`), products run bf16 x bf16
                    (the MXU operand format) and every product is
                    upcast to f32 (`acc`) before the tiny row sums and
                    the edge-axis segment reductions accumulate it —
                    bf16 storage, f32 accumulation.
    """
    if bf16_ops:
        def vec(x):
            return x.astype(jnp.bfloat16)

        def acc(x):
            return x.astype(jnp.float32)

        return _ident, vec, acc
    if mixed_precision:
        def up(x):
            return x.astype(jnp.float32)

        return up, _ident, _ident
    return _ident, _ident, _ident


def _edge_cam_to_pt_explicit(W, pe, cd, pd, up, acc=_ident):
    """W^T applied per edge: [cd, nE] camera rows -> [pd, nE]."""
    return jnp.stack([
        sum(acc(up(W[a * pd + b]) * pe[a]) for a in range(cd))
        for b in range(pd)
    ])


def _edge_pt_to_cam_explicit(W, qe, cd, pd, up, acc=_ident):
    """W applied per edge: [pd, nE] point rows -> [cd, nE]."""
    return jnp.stack([
        sum(acc(up(W[a * pd + b]) * qe[b]) for b in range(pd))
        for a in range(cd)
    ])


def _edge_cam_to_pt_fwd(Jc, Jp, pe, cd, pd, od, up, acc=_ident, vec=_ident):
    """Jp^T (Jc p) per edge via the [od] residual components.

    `vec` re-downcasts the f32-accumulated [od] intermediate before the
    second product under the bf16 pipeline (bf16 operands throughout,
    f32 sums only)."""
    u = [vec(sum(acc(up(Jc[o * cd + a]) * pe[a]) for a in range(cd)))
         for o in range(od)]
    return jnp.stack([
        sum(acc(up(Jp[o * pd + b]) * u[o]) for o in range(od))
        for b in range(pd)
    ])


def _edge_pt_to_cam_fwd(Jc, Jp, qe, cd, pd, od, up, acc=_ident, vec=_ident):
    """Jc^T (Jp q) per edge via the [od] residual components."""
    u = [vec(sum(acc(up(Jp[o * pd + b]) * qe[b]) for b in range(pd)))
         for o in range(od)]
    return jnp.stack([
        sum(acc(up(Jc[o * cd + a]) * u[o]) for o in range(od))
        for a in range(cd)
    ])


def make_coupling_matvecs(
    W: Optional[jax.Array],
    Jc: jax.Array,
    Jp: jax.Array,
    cam_idx: jax.Array,
    pt_idx: jax.Array,
    num_cameras: int,
    num_points: int,
    compute_kind: ComputeKind,
    axis_name: Optional[str] = None,
    mixed_precision: bool = False,
    cam_sorted: bool = False,
    plans: Optional[DualPlans] = None,
    bf16_ops: bool = False,
    bf16_collectives: bool = False,
    fused_kernels: bool = False,
) -> Tuple[Callable[[jax.Array], jax.Array], Callable[[jax.Array], jax.Array]]:
    """Build hpl(q_pt [pd,Np])->[cd,Nc] and hlp(p_cam [cd,Nc])->[pd,Np].

    EXPLICIT mode reads only `W` (per-edge coupling rows [cd*pd, nE]);
    IMPLICIT mode reads only `Jc`/`Jp` rows.  Edge arrays are
    shard-local; outputs are psum-reduced to replicated.

    With `plans` (the TPU fast path) every segment reduction is a
    block-aligned tiled MXU reduction and every vertex->edge expansion a
    tiled one-hot matmul (ops/segtiles.py): `Jc`/`W` live in cam-slot
    order, `Jp` in pt-slot order, and per-edge intermediates hop between
    the orders via the 2-3 row cross permutes — the only non-streaming
    traffic in the whole product.  This replaces the reference's
    cuSPARSE SpMVs / implicitEMulx-ETMulx scatter kernels
    (schur_pcg_solver.cu:315-366, implicit_schur_pcg_solver.cu:20-90).

    `mixed_precision` (BASELINE.md config 5) expects the edge operands to
    be pre-equilibrated and bf16-cast (see schur_pcg_solve); products are
    computed after upcast to float32, so only the stored rows — the PCG's
    bandwidth-dominant traffic — are halved, while Krylov vectors,
    reductions and the preconditioner stay float32.

    `bf16_ops` (SolverOption.bf16) is the rung below: the stored rows
    stay bf16 THROUGH the multiply and the gathered Krylov-vector rows
    are downcast to match — bf16 x bf16 products (the MXU operand
    format, and half the HBM traffic of the edge-expanded transients)
    with every accumulation upcast to f32 first (`_edge_precision`).
    The segment reductions and psums still run on the f32-accumulated
    rows unless `bf16_collectives` ALSO compresses the wire payload to
    bf16 (parallel/mesh.collective_payload_cast) — schur_pcg_solve
    builds the compressed pair only for the S·p matvec the PCG body
    dispatches, never for the once-per-solve RHS/back-substitution
    products.  Requires the XLA (plans=None) lowering.
    """
    up, vec, acc = _edge_precision(mixed_precision, bf16_ops)
    use_fused = (fused_kernels and plans is not None
                 and plans.fused_to_pt is not None
                 and plans.fused_to_cam is not None)
    if (bf16_ops and plans is not None and not use_fused
            and compute_kind != ComputeKind.EXPLICIT):
        raise NotImplementedError(
            "SolverOption.bf16 does not compose with the tiled "
            "coupling kernels in IMPLICIT mode (ops/segtiles."
            "coupling_expand has no bf16 operand path); either lower "
            "with use_tiled=False — flat_solve does this automatically "
            "— or enable SolverOption(fused_kernels=True), whose fused "
            "edge-pipeline kernels carry the bf16 operand tiles")
    from megba_tpu.parallel.mesh import collective_payload_cast

    wire_down, wire_up = collective_payload_cast(
        bf16_collectives and axis_name is not None)

    def psum(x):
        if axis_name is None:
            return x
        with jax.named_scope("megba.collective"):
            return wire_up(jax.lax.psum(wire_down(x), axis_name))

    if use_fused:
        # Fused edge-pipeline dispatch (ops/fused.py): ONE kernel per
        # direction — the Krylov-vector expansion, the coupling
        # contraction, and the segment reduction happen on the same
        # VMEM-resident edge tile.  The coupling rows are permuted into
        # each direction's bucket order ONCE here (outside the matvec
        # closures, so every CG iteration reuses the copies); padding
        # columns are zeroed by the permute, so the kernels need no
        # mask operand.  Off-TPU the same kernel bodies run under
        # Pallas interpret mode — the CPU-lane parity certificate.
        from megba_tpu.ops import fused as _fused

        fp_tp = plans.fused_to_pt
        fp_tc = plans.fused_to_cam
        interp = not _fused.kernels_supported()

        if compute_kind == ComputeKind.EXPLICIT:
            W_tp = _fused.permute_rows(W, fp_tp)
            W_tc = _fused.permute_rows(W, fp_tc)

            def hlp(p_cam: jax.Array) -> jax.Array:
                return psum(_fused.fused_coupling_apply(
                    W_tp, p_cam, fp_tp, w_in_major=True,
                    bf16_operands=bf16_ops, interpret=interp,
                    name="fused_coupling_w_cam_to_pt"))

            def hpl(q_pt: jax.Array) -> jax.Array:
                return psum(_fused.fused_coupling_apply(
                    W_tc, q_pt, fp_tc, w_in_major=False,
                    bf16_operands=bf16_ops, interpret=interp,
                    name="fused_coupling_w_pt_to_cam"))

        else:
            # The tiled lowering stores Jp in PT-slot order (the
            # coupling_reduce convention); the fused plans index the
            # CAM-slot stream, so bring Jp over first (one extra row
            # permute per solve, amortised across CG iterations).  The
            # dtype is pinned back: cam.mask is f32 and would silently
            # promote bf16-stored rows.
            Jp_cam = plans.to_cam(Jp).astype(Jp.dtype)
            Jc_tp = _fused.permute_rows(Jc, fp_tp)
            Jp_tp = _fused.permute_rows(Jp_cam, fp_tp)
            Jc_tc = _fused.permute_rows(Jc, fp_tc)
            Jp_tc = _fused.permute_rows(Jp_cam, fp_tc)

            def hlp(p_cam: jax.Array) -> jax.Array:
                return psum(_fused.fused_coupling_apply_implicit(
                    Jc_tp, Jp_tp, p_cam, fp_tp,
                    bf16_operands=bf16_ops, interpret=interp,
                    name="fused_coupling_j_cam_to_pt"))

            def hpl(q_pt: jax.Array) -> jax.Array:
                return psum(_fused.fused_coupling_apply_implicit(
                    Jp_tc, Jc_tc, q_pt, fp_tc,
                    bf16_operands=bf16_ops, interpret=interp,
                    name="fused_coupling_j_pt_to_cam"))

        return hpl, hlp

    if plans is not None:
        uk = plans.use_kernels

        if compute_kind == ComputeKind.EXPLICIT:
            cdpd = W.shape[0]

            def hlp(p_cam: jax.Array) -> jax.Array:
                cd = p_cam.shape[0]
                pd = cdpd // cd
                pe = vec(seg_expand(p_cam, plans.cam, uk,
                                    name="tile_expand_cam_to_edge"))
                te = _edge_cam_to_pt_explicit(W, pe, cd, pd, up, acc)
                return psum(seg_reduce(plans.to_pt(te), plans.pt, uk,
                                       name="tile_reduce_edge_to_pt"))

            def hpl(q_pt: jax.Array) -> jax.Array:
                pd = q_pt.shape[0]
                cd = cdpd // pd
                qe = vec(plans.to_cam(seg_expand(
                    q_pt, plans.pt, uk,
                    name="tile_expand_pt_to_edge")))  # [pd, nCamSlots]
                te = _edge_pt_to_cam_explicit(W, qe, cd, pd, up, acc)
                return psum(seg_reduce(te, plans.cam, uk,
                                       name="tile_reduce_edge_to_cam"))

        else:
            from megba_tpu.ops.segtiles import (
                coupling_expand,
                coupling_reduce,
            )

            ocd, opd = Jc.shape[0], Jp.shape[0]

            def hlp(p_cam: jax.Array) -> jax.Array:
                cd = p_cam.shape[0]
                od = ocd // cd
                pd = opd // od
                # u = Jc p per edge (fused gather+matvec, cam order); the
                # [od] rows hop to pt order; J^T u reduces to points
                # (fused matvec+reduce).  The expanded [cd]/[pd] per-edge
                # rows never touch HBM.
                u = coupling_expand(p_cam, Jc, plans.cam, cd, uk,
                                    name="coupling_expand_cam_to_edge")
                u_pt = plans.to_pt(u)
                return psum(coupling_reduce(
                    Jp, u_pt, plans.pt, pd, uk,
                    name="coupling_reduce_edge_to_pt"))

            def hpl(q_pt: jax.Array) -> jax.Array:
                pd = q_pt.shape[0]
                od = opd // pd
                cd = ocd // od
                u = coupling_expand(q_pt, Jp, plans.pt, pd, uk,
                                    name="coupling_expand_pt_to_edge")
                u_cam = plans.to_cam(u)
                return psum(coupling_reduce(
                    Jc, u_cam, plans.cam, cd, uk,
                    name="coupling_reduce_edge_to_cam"))

        return hpl, hlp

    if compute_kind == ComputeKind.EXPLICIT:
        cdpd = W.shape[0]
        # cd/pd from the gathered vector shapes at call time.

        def hlp(p_cam: jax.Array) -> jax.Array:
            cd = p_cam.shape[0]
            pd = cdpd // cd
            pe = vec(gather_fm(p_cam, cam_idx))  # [cd, nE]
            te = _edge_cam_to_pt_explicit(W, pe, cd, pd, up, acc)
            return psum(segsum_fm(te, pt_idx, num_points))

        def hpl(q_pt: jax.Array) -> jax.Array:
            pd = q_pt.shape[0]
            cd = cdpd // pd
            qe = vec(gather_fm(q_pt, pt_idx))  # [pd, nE]
            te = _edge_pt_to_cam_explicit(W, qe, cd, pd, up, acc)
            return psum(segsum_fm(te, cam_idx, num_cameras,
                                  indices_are_sorted=cam_sorted))

    else:
        ocd, opd = Jc.shape[0], Jp.shape[0]

        def hlp(p_cam: jax.Array) -> jax.Array:
            cd = p_cam.shape[0]
            od = ocd // cd
            pd = opd // od
            pe = vec(gather_fm(p_cam, cam_idx))
            te = _edge_cam_to_pt_fwd(Jc, Jp, pe, cd, pd, od, up, acc, vec)
            return psum(segsum_fm(te, pt_idx, num_points))

        def hpl(q_pt: jax.Array) -> jax.Array:
            pd = q_pt.shape[0]
            od = opd // pd
            cd = ocd // od
            qe = vec(gather_fm(q_pt, pt_idx))
            te = _edge_pt_to_cam_fwd(Jc, Jp, qe, cd, pd, od, up, acc, vec)
            return psum(segsum_fm(te, cam_idx, num_cameras,
                                  indices_are_sorted=cam_sorted))

    return hpl, hlp


def make_matvec_2d(
    W: Optional[jax.Array],
    Jc: Optional[jax.Array],
    Jp: Optional[jax.Array],
    tile_plan,
    pt_idx: jax.Array,
    Hpp_d: jax.Array,
    Hll_inv: jax.Array,
    num_cameras: int,
    num_points: int,
    compute_kind: ComputeKind,
    axis_name,
    mixed_precision: bool = False,
    bf16_ops: bool = False,
    bf16_collectives: bool = False,
    fused_kernels: bool = False,
):
    """Build the fused 2-D Schur matvec S·p (camera x edge mesh).

    The 1-D matvec's two WORLD-wide psums (solver/pcg.s_matvec via
    make_coupling_matvecs) become subgroup-scoped stages on the
    (EDGE_AXIS, CAM_AXIS) mesh, with the point-shard transfer
    double-buffered against the tile contraction:

      1. camera gather — LOCAL: every edge of device (e, c) touches a
         camera inside tile c (the camera-tile plan routed it there),
         so Jc·p / W·p reads this device's own tile slice of the
         replicated p.  Zero bytes.
      2. point reduction — psum_scatter over CAM_AXIS (each camera
         column takes ownership of one point shard of the partial
         scatter), then psum over the EDGE subgroup: the full [pd, Np]
         all-reduce of the 1-D path shrinks to one (C-1)/C scatter plus
         a 1/C-sized subgroup reduce.
      3. Hll⁻¹ — applied to the OWNED shard only (replicated rows,
         local slice).
      4. tile loop with DOUBLE BUFFERING: the owned point shard rotates
         around the CAM_AXIS ring (C-1 collective_permutes); at step j
         the ppermute fetching shard j+1 is issued BEFORE the
         contraction of shard j (the plan's co-observation-ordered
         bucket of edges touching it), so the ICI transfer of the next
         tile overlaps the MXU contraction of the current one.
      5. camera reduction — psum over the EDGE subgroup of the [cd, Tc]
         tile partials (1/C of the 1-D payload), then one all_gather
         over CAM_AXIS re-replicates the result.

    Every collective of THIS matvec is subgroup-scoped (replica groups
    of size E or C, never E*C — the `ba_2d_w4_f32` canonical program
    pins the census; TWO_LEVEL/MULTILEVEL coarse-correction psums in
    precond_apply still span the full axis tuple — see ARCHITECTURE),
    and the per-iteration bytes moved are strictly below the 1-D
    all-reduce scaling law (analysis/hlo.collective_bytes_moved is the
    model; the budget gate's `collective_bytes_per_sp` axis pins it).
    The CG scalars read replicated values and stay collective-free, as
    on the 1-D mesh.

    Returns a replicated-in/replicated-out `s_matvec(p)` — drop-in for
    the 1-D closure, so guards, forcing, warm starts and every
    preconditioner family compose unchanged.  Under `mixed_precision`
    the contract matches the 1-D path (bf16 edge rows upcast before
    every product, f32 Krylov vectors and accumulation — `p` is f32 by
    construction), but agreement with the 1-D result is only at the
    accuracy of the bf16-rounded operator (~1e-3 on ill-conditioned
    scenes): the per-column summation grouping differs, and a PCG run
    to stagnation resolves the operator's own rounding, not the
    grouping (tests/test_mesh2d.py compose test pins this at 1e-2).

    `bf16_ops` / `bf16_collectives` are the bf16 MXU pipeline
    (SolverOption.bf16 / .bf16_collectives): the per-edge coupling
    products run on bf16 operands with f32 accumulation
    (`_edge_precision`, the same discipline as the 1-D closures), and
    the collective gate additionally casts EVERY payload of this
    matvec's in-body collectives — the camera psum_scatter, both
    edge-subgroup psums, the C-1 ring permutes and the final camera
    all_gather — to bf16 on the wire, halving the already-subgroup-
    scoped `collective_bytes_per_sp` once more.  Both gates off lower
    byte-identically to the PR 14 pipeline.

    `fused_kernels` swaps the RING-STEP contraction (step 4's
    gather -> per-edge product -> camera segsum) for one fused Pallas
    kernel call per step (ops/fused.fused_single_block_apply): the
    rotating point shard is the kernel's single input block and the
    camera tile its single output block, so the per-edge expanded rows
    of each bucket stay VMEM-resident.  Steps 1-3 and 5 (the local
    camera gather, the subgroup collectives, Hll⁻¹) are unchanged —
    the stage-1 point reduction keeps its XLA segsum, honestly
    documented as outside the fused surface.
    """
    edge_axis, cam_axis = axis_name
    C = tile_plan.cam_blocks
    Tc = tile_plan.tile_cams
    Sp = tile_plan.shard_points
    nc_pad = C * Tc
    np_pad = C * Sp
    cdpd = None if W is None else W.shape[0]
    ocd = None if Jc is None else Jc.shape[0]
    opd = None if Jp is None else Jp.shape[0]

    up, vec, pacc = _edge_precision(mixed_precision, bf16_ops)
    from megba_tpu.parallel.mesh import collective_payload_cast

    wire_down, wire_up = collective_payload_cast(bf16_collectives)
    if fused_kernels:
        from megba_tpu.ops import fused as _fused

        fused_interp = not _fused.kernels_supported()

    # Replicated solve quantities, padded once to the tile geometry so
    # tile/shard slices are static-shape.  Zero padding is inert: padded
    # cameras/points have zero coupling rows and are never gathered by a
    # real (unmasked) edge.
    Hpp_pad = jnp.pad(Hpp_d, ((0, nc_pad - num_cameras), (0, 0), (0, 0)))
    Hll_inv_pad = jnp.pad(Hll_inv, ((0, 0), (0, np_pad - num_points)))
    ring = [(i, (i - 1) % C) for i in range(C)]

    @jax.named_scope("megba.matvec_2d")
    def s_matvec(p: jax.Array) -> jax.Array:
        cd = p.shape[0]
        ci = jax.lax.axis_index(cam_axis)
        p_pad = jnp.pad(p, ((0, 0), (0, nc_pad - num_cameras)))
        p_t = jax.lax.dynamic_slice_in_dim(p_pad, ci * Tc, Tc, axis=1)
        # (1) local camera gather + per-edge coupling product.
        pe = vec(gather_fm(p_t, tile_plan.cam_local))  # [cd, nE_loc]
        if compute_kind == ComputeKind.EXPLICIT:
            pd = cdpd // cd
            te = _edge_cam_to_pt_explicit(
                W, pe, cd, pd, up, pacc)  # [pd, nE_loc]
        else:
            od = ocd // cd
            pd = opd // od
            te = _edge_cam_to_pt_fwd(Jc, Jp, pe, cd, pd, od, up, pacc, vec)
        # (2) point reduction: scatter over CAM, reduce over EDGE — the
        # wire casts compress both stage payloads to bf16 under the
        # collective gate (the shard stays compressed between the two).
        t_part = segsum_fm(te, pt_idx, np_pad)
        with jax.named_scope("megba.collective"):
            t_sh = jax.lax.psum_scatter(wire_down(t_part), cam_axis,
                                        scatter_dimension=1, tiled=True)
            t_sh = wire_up(jax.lax.psum(t_sh, edge_axis))  # [pd, Sp] owned
        # (3) Hll^-1 on the owned shard.
        hll_sh = jax.lax.dynamic_slice_in_dim(
            Hll_inv_pad, ci * Sp, Sp, axis=1)
        cur = block_matvec_fm(hll_sh, t_sh)
        # (4) double-buffered tile loop: issue the fetch of shard j+1,
        # THEN contract shard j's co-observation bucket.  Under the
        # collective gate the rotating point shard rides the ring as
        # bf16 (each permute moves half the bytes); the contraction
        # consumes it through the same bf16-operand policy as step 1.
        cur = wire_down(cur)
        tile_acc = jnp.zeros((cd, Tc), p.dtype)
        for j in range(C):
            nxt = cur
            if j < C - 1:
                with jax.named_scope("megba.collective"):
                    nxt = jax.lax.ppermute(cur, cam_axis, perm=ring)
            s = (ci + j) % C  # j, C static ints: stays the index dtype
            slot = jax.lax.dynamic_slice_in_dim(
                tile_plan.bucket_slot, s, 1, axis=0)[0]
            ptl = jax.lax.dynamic_slice_in_dim(
                tile_plan.bucket_ptl, s, 1, axis=0)[0]
            mk = jax.lax.dynamic_slice_in_dim(
                tile_plan.bucket_mask, s, 1, axis=0)[0]
            cl = jnp.take(tile_plan.cam_local, slot)
            if fused_kernels:
                # Fused ring step: the shard gather, the coupling
                # product and the camera-tile reduction run in ONE
                # kernel over this step's co-observation bucket.  The
                # mask moves from the gathered vector onto the coupling
                # rows (padding pairs get zero rows — same algebra, and
                # the kernel then needs no mask operand).
                mkd = mk.astype(W.dtype if W is not None else Jc.dtype)
                if compute_kind == ComputeKind.EXPLICIT:
                    Wg = jnp.take(W, slot, axis=1) * mkd
                    step = _fused.fused_single_block_apply(
                        Wg, cur, ptl, cl, out_block=Tc,
                        w_in_major=False, bf16_operands=bf16_ops,
                        interpret=fused_interp,
                        name="fused_ring_w_pt_to_cam")
                else:
                    Jcg = jnp.take(Jc, slot, axis=1)
                    Jpg = jnp.take(Jp, slot, axis=1) * mkd
                    step = _fused.fused_single_block_apply(
                        Jpg, cur, ptl, cl, out_block=Tc,
                        rows_out=Jcg, bf16_operands=bf16_ops,
                        interpret=fused_interp,
                        name="fused_ring_j_pt_to_cam")
                tile_acc = tile_acc + step.astype(p.dtype)
                cur = nxt
                continue
            cur_g = vec(gather_fm(cur, ptl))
            qe = cur_g * mk.astype(cur_g.dtype)  # [pd, Lb]
            if compute_kind == ComputeKind.EXPLICIT:
                Wg = up(jnp.take(W, slot, axis=1))
                contrib = _edge_pt_to_cam_explicit(
                    Wg, qe, cd, pd, _ident, pacc)
            else:
                Jcg = up(jnp.take(Jc, slot, axis=1))
                Jpg = up(jnp.take(Jp, slot, axis=1))
                contrib = _edge_pt_to_cam_fwd(
                    Jcg, Jpg, qe, cd, pd, od, _ident, pacc, vec)
            tile_acc = tile_acc + segsum_fm(contrib.astype(p.dtype), cl, Tc)
            cur = nxt
        # (5) camera reduction: EDGE-subgroup psum of the tile, one
        # all_gather over CAM re-replicates (both payloads wire-cast
        # under the collective gate).
        with jax.named_scope("megba.collective"):
            hpl_t = wire_up(jax.lax.psum(wire_down(tile_acc), edge_axis))
        y_t = cam_block_matvec(
            jax.lax.dynamic_slice_in_dim(Hpp_pad, ci * Tc, Tc, axis=0),
            p_t) - hpl_t
        with jax.named_scope("megba.collective"):
            y = wire_up(all_gather_invariant(wire_down(y_t), cam_axis,
                                             axis=1, tiled=True))
        return y[:, :num_cameras]

    return s_matvec


# named_scope: the PCG while_loop (body traced inside this call) carries
# a navigable label in profiler traces — see observability/__init__.py.
@jax.named_scope("megba.pcg_core")
def _pcg_core(matvec, precond, b, max_iter, tol, refuse_ratio, tol_relative,
              x0=None, guard=False, max_restarts=0, fused=True):
    """Preconditioned CG over an arbitrary pytree "vector".

    One implementation of the reference's stopping + refuse semantics
    (|rho| < tol exit, schur_pcg_solver.cu:406-407; rho > refuse_ratio *
    min(rho) -> restore best iterate, :288-296) shared by the Schur
    solver (vector = one array) and the plain full-system solver
    (vector = a (camera, point) pair).  Returns
    (x, iterations, rho, r0_ratio, restarts, broken).

    The body is the Chronopoulos-Gear single-recurrence CG: carrying the
    auxiliary direction s = A p alongside p lets each iteration run as
    ONE pass.  The matvec comes first: w = A u and delta = <u, w>, then
    alpha from the scalar recurrence alpha = gamma / (delta - beta *
    gamma / alpha_prev) with beta = gamma / gamma_prev, four fused axpys
    (p, s, x, r), the one preconditioner apply u = M^-1 r and the next
    gamma = <r, u>.  The first iteration takes classic CG's first step
    (p = u, s = A u, alpha = gamma / delta).  Iterates are identical to
    textbook PCG in exact arithmetic, and k iterations run exactly k
    matvecs: the product an iteration that `cond` refuses would need is
    never computed.  The matvec stays the only collective site, so the
    census invariant — exactly 2 all-reduces per S·p inside the while
    body (analysis/program_audit.py pass 2) — is unchanged.

    `guard=True` (RobustOption.guards) arms breakdown detection on the
    Chronopoulos-Gear scalars: a non-finite or sign-flipped gamma or
    delta means the recurrence has left the SPD regime, and the guard
    performs an in-loop COLD RESTART from the current iterate: one
    body iteration repurposes the body's single matvec slot to
    recompute the true residual r = b - A x (with u = M^-1 r and
    gamma), and the next takes the first-iteration step from it.  A
    step whose delta broke is not taken.  At most `max_restarts`
    restarts; one more breakdown exits with `broken=True` and the best
    iterate.  Restart iterations use the SAME matvec slot, so the
    per-body-iteration collective census (2 all-reduces for the Schur
    S.p) is unchanged — the `ba_guarded_w2_f32` canonical program pins
    exactly this.  When no breakdown fires every selected value is
    bitwise identical to the unguarded body.

    `x0` warm-starts the iteration (r0 = b - A x0: the one matvec
    outside the while body).  `tol_relative` anchors the threshold
    to the RHS energy <b, M^-1 b> — NOT the initial-guess residual
    rho0, which a good warm start drives toward _TINY_RHO and which
    would therefore either exit spuriously after 0 iterations or
    over-solve relative to an already-tiny baseline.  For x0=None the
    two anchors coincide bitwise (r0 = b).

    `fused=False` selects the TEXTBOOK-recurrence body (the bf16 MXU
    pipeline's body): the Chronopoulos-Gear fusion carries s = A·p by
    LINEARITY (s ← w + beta s), and a bf16-operand matvec is slightly
    nonlinear in its input (the vector is rounded to bf16 per apply),
    so the carried s drifts from the true A·p by ~eps_bf16 per
    iteration — measured on small BA systems the fused recurrence
    collapses (negative gamma/delta, garbage iterates) within ~20
    iterations.  The textbook body recomputes s = A·p FRESH each
    iteration: same per-iteration op counts (one matvec, one precond
    apply, two compensated dots) and the matvec stays the only
    collective site (2 all-reduces per S·p in the body — the
    `ba_bf16_w2_f32` canonical program pins it).  Warm starts, refuse,
    guards and restarts keep their semantics; a guarded restart is ONE
    refresh iteration in both bodies (classic CG refreshes r = b - A x
    and re-seeds p = M⁻¹ r).
    """
    tm = jax.tree_util.tree_map

    def tdot(a, c):
        return jax.tree_util.tree_reduce(
            lambda acc, v: acc + v, tm(_dot, a, c))

    def axpy(a, x, y):  # y + a * x, leafwise
        return tm(lambda xi, yi: yi + a * xi, x, y)

    def select(pred, a, c):
        return tm(lambda ai, ci: jnp.where(pred, ai, ci), a, c)

    if x0 is None:
        x_init = tm(jnp.zeros_like, b)
        r0 = b  # x0 = 0 so r0 = b - A x0 = b
        u0 = precond(r0)
        rho0 = tdot(r0, u0)
        rhs_energy = rho0  # r0 IS b: reuse, bitwise-identical threshold
        r0_ratio = jnp.ones_like(rho0)
    else:
        x_init = x0
        r0 = axpy(jnp.asarray(-1.0, jax.tree_util.tree_leaves(b)[0].dtype),
                  matvec(x0), b)
        u0 = precond(r0)
        rho0 = tdot(r0, u0)
        ub = precond(b)
        rhs_energy = tdot(b, ub)
        # Diagnostic first, then the safeguard: a warm start whose
        # residual energy EXCEEDS the RHS energy is a worse start than
        # zero (the trust region moved the damped system out from under
        # the previous step) — fall back to the cold start, which is
        # fully available from the quantities just computed.  The
        # recorded ratio stays raw so the trace shows warm-start quality
        # honestly (values > 1 mean "fell back").
        r0_ratio = jnp.abs(rho0) / jnp.maximum(
            jnp.abs(rhs_energy), jnp.asarray(_TINY_RHO, rho0.dtype))
        use_ws = jnp.abs(rho0) <= jnp.abs(rhs_energy)
        x_init = select(use_ws, x_init, tm(jnp.zeros_like, b))
        r0 = select(use_ws, r0, b)
        u0 = select(use_ws, u0, ub)
        rho0 = jnp.where(use_ws, rho0, rhs_energy)
    # Reference semantics: absolute threshold on rho; tol_relative scales
    # it by the RHS energy, floored so a zero RHS exits immediately
    # instead of iterating into 0/0 NaNs.
    threshold = (
        jnp.maximum(tol * jnp.abs(rhs_energy),
                    jnp.asarray(_TINY_RHO, rho0.dtype))
        if tol_relative else tol
    )

    if not fused:
        return _pcg_core_classic(
            matvec, precond, b, max_iter, threshold, refuse_ratio,
            x_init, r0, u0, rho0, rhs_energy, r0_ratio,
            guard, max_restarts, tdot, axpy, select)

    # The matvec is the FIRST step of the body, so the loop runs exactly
    # one product per CG iteration: the product of an iteration that
    # `cond` refuses is never computed.  The body's first iteration (and,
    # under the guard, the first after a restart refresh) takes classic
    # CG's first step: p = u, s = A u, alpha = rho / <u, A u> (the
    # division guarded for an operator that annihilates u).
    zeros = tm(jnp.zeros_like, u0)
    one = jnp.ones_like(rho0)

    # u = M^-1 r crosses the loop boundary transposed: a (d, n) leaf is
    # carried in the (n, d) orientation in which the block-diagonal
    # einsums (`nij,jn->in`) read it, so the matvec that opens the body
    # reads the carried value with no relayout between them.  (Carried
    # as (d, n), XLA's CPU backend puts a transpose of the loop
    # parameter there, an instruction of no layer; on the TPU both
    # orientations compile to the same physical layout.)
    def flip(v):
        return tm(jnp.transpose, v)

    def cg_step(first, x, r, u, rho, rho_prev, alpha_prev, p, s, w):
        """One Chronopoulos-Gear step from w = A u: the scalar recurrence
        alpha = rho / (delta - beta * rho / alpha_prev), then the four
        fused axpys (p, s, x, r)."""
        delta = tdot(u, w)
        beta = jnp.where(first, jnp.zeros_like(rho), rho / rho_prev)
        alpha = jnp.where(
            first, rho / jnp.where(delta == 0, one, delta),
            rho / (delta - beta * rho / alpha_prev))
        p = axpy(beta, p, u)  # u + beta p: u on a first step, p = 0
        s = axpy(beta, s, w)  # w + beta s == A p
        return delta, alpha, p, s, axpy(alpha, p, x), axpy(-alpha, s, r)

    if not guard:
        state0 = (jnp.int32(0), x_init, r0, flip(u0), rho0, rho0, one,
                  zeros, zeros, jnp.abs(rho0), x_init, jnp.bool_(False))

        def cond(state):
            k, _, _, _, rho, _, _, _, _, _, _, refused = state
            return (k < max_iter) & (jnp.abs(rho) >= threshold) & (~refused)

        def body(state):
            (k, x, r, u, rho, rho_prev, alpha_prev, p, s, rho_min, x_best,
             _) = state
            u = flip(u)
            # The only matvec (the sole collective site: 2 psums inside
            # the Schur S·p), then one fused vector pass...
            w = matvec(u)
            _, alpha, p, s, x, r = cg_step(k == 0, x, r, u, rho, rho_prev,
                                           alpha_prev, p, s, w)
            # ...the only preconditioner apply and the residual's dot.
            u = precond(r)
            rho_new = tdot(r, u)
            refused = jnp.abs(rho_new) > refuse_ratio * rho_min
            improved = jnp.abs(rho_new) < rho_min
            rho_min = jnp.where(improved, jnp.abs(rho_new), rho_min)
            x_best = select(improved, x, x_best)
            return (k + 1, x, r, flip(u), rho_new, rho, alpha, p, s,
                    rho_min, x_best, refused)

        (k, x, _, _, rho, _, _, _, _, _, x_best,
         refused) = jax.lax.while_loop(cond, body, state0)
        return (select(~refused, x, x_best), k, rho, r0_ratio,
                jnp.int32(0), jnp.bool_(False))

    # ---- guarded body (RobustOption.guards) -----------------------------
    # A 3-mode branchless body: phase 0 = CG step, phase 1 = the
    # restart's residual refresh (the matvec slot computes A x, then
    # r := b - A x, u = M^-1 r, rho = <r, u>), phase 2 = the first CG
    # step, taken on entry and after every refresh.  Every mode runs the
    # SAME one matvec + one precond, so the body's collective census is
    # identical to the unguarded body's; with no breakdown every selected
    # value is the unguarded one, bitwise.
    threshold_arr = jnp.asarray(threshold, rho0.dtype)
    # Keep-alive rho carried out of a breakdown: strictly above the exit
    # threshold so cond cannot fire on a placeholder, finite by
    # construction (|rhs_energy| is, or the solve was empty).
    keepalive = jnp.maximum(jnp.abs(rhs_energy), threshold_arr) * 2.0 + 1.0
    minus_one = jnp.asarray(-1.0, rho0.dtype)

    state0 = (jnp.int32(0), x_init, r0, flip(u0), rho0, rho0, one, zeros,
              zeros, jnp.abs(rho0), x_init, jnp.bool_(False),
              jnp.int32(2), jnp.int32(0), jnp.bool_(False))

    def cond(state):
        k, _, _, _, rho, _, _, _, _, _, _, refused, _, _, broken = state
        return ((k < max_iter) & (jnp.abs(rho) >= threshold)
                & (~refused) & (~broken))

    def body(state):
        (k, x, r, u, rho, rho_prev, alpha_prev, p, s, rho_min, x_best,
         refused, phase, restarts, broken) = state
        u = flip(u)
        refresh = phase == 1
        # The one matvec: A u normally, A x during the residual refresh.
        w = matvec(select(refresh, x, u))
        delta, alpha, p, s, x_new, r_new = cg_step(
            phase == 2, x, r, u, rho, rho_prev, alpha_prev, p, s, w)
        # Breakdown: the SPD invariants delta = <u, A u> >= 0 and
        # gamma = <r, M^-1 r> >= 0 broke, or the scalars left the finite
        # range.  A refresh's delta is no real scalar (w is A x); a step
        # whose delta broke is not taken.
        delta_bad = (~refresh) & (~jnp.isfinite(delta) | (delta < 0))
        take = (~refresh) & (~delta_bad)
        x = select(take, x_new, x)
        # A step not taken leaves p = s = 0 for the first step after it.
        p = select(take, p, zeros)
        s = select(take, s, zeros)
        r = select(refresh, axpy(minus_one, w, b),  # b - A x
                   select(take, r_new, r))
        u = precond(r)
        rho_new = tdot(r, u)
        breakdown = delta_bad | ~jnp.isfinite(rho_new) | (rho_new < 0)
        enter = breakdown & (restarts < max_restarts)
        broken = broken | (breakdown & (restarts >= max_restarts))
        phase_next = jnp.where(enter, jnp.int32(1),
                               jnp.where(refresh, jnp.int32(2),
                                         jnp.int32(0)))
        restarts = restarts + enter.astype(jnp.int32)
        ok = take & ~breakdown
        rho_next = jnp.where(enter, keepalive, rho_new)
        refused = ok & (jnp.abs(rho_new) > refuse_ratio * rho_min)
        improved = ok & (jnp.abs(rho_new) < rho_min)
        rho_min = jnp.where(improved, jnp.abs(rho_new), rho_min)
        x_best = select(improved, x, x_best)
        return (k + 1, x, r, flip(u), rho_next, rho, alpha, p, s, rho_min,
                x_best, refused, phase_next, restarts, broken)

    (k, x, _, _, rho, _, _, _, _, _, x_best, refused, _, restarts,
     broken) = jax.lax.while_loop(cond, body, state0)
    return (select(~refused & ~broken, x, x_best), k, rho, r0_ratio,
            restarts, broken)


def _pcg_core_classic(matvec, precond, b, max_iter, threshold, refuse_ratio,
                      x_init, r0, u0, rho0, rhs_energy, r0_ratio,
                      guard, max_restarts, tdot, axpy, select):
    """The textbook-recurrence PCG body (`_pcg_core(fused=False)`).

    Iterates are textbook PCG: p ← u + beta p, s = A p computed FRESH,
    alpha = rho / <p, s>.  Same per-iteration op census as the fused
    body (one matvec — the only collective site — one precond apply,
    two compensated dots); no priming matvec is needed (there is no
    auxiliary recurrence), so the matvec count is exactly k (+1 per
    warm start / restart refresh).  Stopping, refuse-best-iterate,
    breakdown-guard and restart semantics mirror the fused body; a
    guarded restart is ONE iteration whose matvec slot computes A x
    for the residual refresh r = b - A x, p = M⁻¹ r.

    This body exists for the bf16 MXU pipeline, whose operand-rounded
    matvec is nonlinear at the bf16-eps scale — see _pcg_core's
    docstring for why the fused recurrence collapses there.

    STAGNATION-EXIT semantics (the precision-aware part): a FINITE
    sign flip in the SPD scalars (gamma = <r, M⁻¹r> < 0 or
    delta = <p, A p> < 0) is not treated as a recurrence fault — on a
    bf16-operand operator it is the signature of the iterate reaching
    the operator's resolution (the quadratic forms of an eps_bf16-
    nonlinear apply go indefinite exactly when the residual
    concentrates in directions the rounding can no longer resolve;
    measured: restart-and-retry at that point re-breaks within a few
    iterations and escalates into LM recoveries on perfectly clean
    solves).  The solve instead restores the BEST iterate and exits —
    the same restore-and-stop contract as the reference's refuse
    guard, extended from "rho grew" to "rho left the SPD cone".
    Non-finite scalars (actual poison) keep the full breakdown /
    restart / broken ladder under `guard`.
    """
    def safe_div(num, den):
        return num / jnp.where(den == 0, jnp.ones_like(den), den)

    if not guard:
        state0 = (jnp.int32(0), x_init, r0, u0, rho0,
                  jnp.abs(rho0), x_init, jnp.bool_(False))

        def cond(state):
            k, _, _, _, rho, _, _, refused = state
            return (k < max_iter) & (jnp.abs(rho) >= threshold) & (~refused)

        def body(state):
            k, x, r, p, rho, rho_min, x_best, refused = state
            s = matvec(p)
            delta = tdot(p, s)
            alpha = safe_div(rho, delta)
            x = axpy(alpha, p, x)
            r = axpy(-alpha, s, r)
            u = precond(r)
            rho_new = tdot(r, u)
            beta = safe_div(rho_new, rho)
            p = axpy(beta, p, u)  # u + beta p
            stall = (rho_new < 0) | (delta < 0)
            refused = stall | (jnp.abs(rho_new) > refuse_ratio * rho_min)
            improved = (~stall) & (jnp.abs(rho_new) < rho_min)
            rho_min = jnp.where(improved, jnp.abs(rho_new), rho_min)
            x_best = select(improved, x, x_best)
            return (k + 1, x, r, p, rho_new, rho_min, x_best, refused)

        (k, x, _, _, rho, _, x_best, refused) = jax.lax.while_loop(
            cond, body, state0)
        return (select(~refused, x, x_best), k, rho, r0_ratio,
                jnp.int32(0), jnp.bool_(False))

    # ---- guarded classic body -------------------------------------------
    # Two phases: 0 = normal step, 1 = restart refresh (the matvec slot
    # computes A x; r := b - A x, p := M⁻¹ r — classic CG carries no
    # auxiliary direction, so one refresh iteration fully restarts the
    # recurrence).  Same census per iteration as the unguarded body; a
    # phase-0 run with no breakdown selects the unguarded values.
    threshold_arr = jnp.asarray(threshold, rho0.dtype)
    keepalive = jnp.maximum(jnp.abs(rhs_energy), threshold_arr) * 2.0 + 1.0
    minus_one = jnp.asarray(-1.0, rho0.dtype)

    state0 = (jnp.int32(0), x_init, r0, u0, rho0,
              jnp.abs(rho0), x_init, jnp.bool_(False),
              jnp.int32(0), jnp.int32(0), jnp.bool_(False))

    def cond(state):
        k, _, _, _, rho, _, _, refused, _, _, broken = state
        return ((k < max_iter) & (jnp.abs(rho) >= threshold)
                & (~refused) & (~broken))

    def body(state):
        (k, x, r, p, rho, rho_min, x_best, refused,
         phase, restarts, broken) = state
        advancing = phase == 0
        refresh = phase == 1
        # The one matvec: A p normally, A x during the refresh.
        w = matvec(select(refresh, x, p))
        delta = tdot(p, w)  # garbage during refresh: masked below
        alpha = safe_div(rho, delta)
        step = jnp.where(advancing, alpha, jnp.zeros_like(alpha))
        x_new = axpy(step, p, x)
        r_new = select(refresh, axpy(minus_one, w, b),  # b - A x
                       axpy(-step, w, r))
        u = precond(r_new)
        rho_new = tdot(r_new, u)
        # Two distinct failure signatures (docstring): a FINITE sign
        # flip of the SPD scalars is the bf16 operator's resolution
        # floor — restore-best-and-stop via the refuse exit, no guard
        # event; non-finite scalars are actual poison and ride the
        # breakdown/restart/broken ladder.  A refresh iteration's
        # delta is stale, but its r/u/rho_new are REAL (the refreshed
        # residual) — so only advancing iterations classify.
        finite = jnp.isfinite(rho_new) & jnp.isfinite(delta)
        stall = advancing & finite & ((rho_new < 0) | (delta < 0))
        breakdown = advancing & ~finite
        enter = breakdown & (restarts < max_restarts)
        broken = broken | (breakdown & (restarts >= max_restarts))
        restarts = restarts + enter.astype(jnp.int32)
        phase_next = jnp.where(enter, jnp.int32(1), jnp.int32(0))
        ok_adv = advancing & ~breakdown & ~stall
        x = select(ok_adv, x_new, x)
        r = select(ok_adv | refresh, r_new, r)
        beta = safe_div(rho_new, rho)
        p = select(refresh, u, select(ok_adv, axpy(beta, p, u), p))
        rho_next = jnp.where(enter, keepalive, rho_new)
        refused = stall | (ok_adv
                           & (jnp.abs(rho_new) > refuse_ratio * rho_min))
        improved = ok_adv & (jnp.abs(rho_new) < rho_min)
        rho_min = jnp.where(improved, jnp.abs(rho_new), rho_min)
        x_best = select(improved, x, x_best)
        return (k + 1, x, r, p, rho_next, rho_min, x_best, refused,
                phase_next, restarts, broken)

    (k, x, _, _, rho, _, x_best, refused, _, restarts,
     broken) = jax.lax.while_loop(cond, body, state0)
    return (select(~refused & ~broken, x, x_best), k, rho, r0_ratio,
            restarts, broken)


def plain_pcg_solve(
    system: SchurSystem,
    Jc: jax.Array,
    Jp: jax.Array,
    cam_idx: jax.Array,
    pt_idx: jax.Array,
    region: jax.Array,
    max_iter: int = 100,
    tol: float = 1e-1,
    refuse_ratio: float = 1.0,
    tol_relative: bool = False,
    compute_kind: ComputeKind = ComputeKind.IMPLICIT,
    axis_name: Optional[str] = None,
    mixed_precision: bool = False,
    cam_sorted: bool = False,
    preconditioner: PreconditionerKind = PreconditionerKind.HPP,
    plans: Optional[DualPlans] = None,
    x0: Optional[Tuple[jax.Array, jax.Array]] = None,
    guard: bool = False,
    max_restarts: int = 0,
    precond: PrecondKind = PrecondKind.JACOBI,
    neumann_order: int = 2,
    cluster_plan=None,
    cam_fixed=None,
    smooth_omega: float = 0.0,
    tile_plan=None,
    bf16: bool = False,
    bf16_collectives: bool = False,
    fused_kernels: bool = False,  # accepted for call-site symmetry;
    # validate_options refuses fused_kernels without use_schur, so the
    # full-system path never sees it True.
) -> PCGResult:
    """Solve the damped FULL system H dx = g without Schur reduction.

    `x0` (a (dx_cam, dx_pt) pair) warm-starts the CG iteration; `tol`
    may be a traced scalar (the inexact-LM forcing path passes eta_k^2
    per LM iteration).

    `preconditioner` is accepted for signature parity and ignored: the
    full system's exact block diagonal (Hpp, Hll) IS this solver's
    preconditioner, so both kinds coincide here.  The same goes for the
    `precond` operator family and its knobs (`neumann_order`,
    `cluster_plan`, `cam_fixed`): the stronger Schur operators are
    BA/Schur-path features (validate_options rejects them with
    use_schur=False), accepted here only so the LM loop can call both
    solvers through one signature.

    The path the reference left as `// TODO(Jie Ren)` behind
    `useSchur=false` (base_problem.cpp:112-123) — implemented here: PCG
    over the concatenated (camera, point) unknowns with the block-diagonal
    H as preconditioner, coupling applied by the same matrix-free /
    per-edge-row matvecs as the Schur solver.  Useful when the point
    blocks are ill-conditioned enough that the Schur complement's
    Hll^-1 amplifies error, and as an independent cross-check of the
    Schur pipeline (both solve the same damped normal equations).
    """
    # Retrace sentinel hook (analysis/retrace.py): counts only under an
    # active jax trace — eager calls are not compilations.
    note_trace("solver.plain_pcg", system.g_cam, system.g_pt, Jc, Jp,
               static=static_key(compute_kind, axis_name, preconditioner))
    num_cameras = system.Hpp.shape[0]
    num_points = system.Hll.shape[1]

    if mixed_precision:
        raise NotImplementedError(
            "mixed_precision is only implemented for the Schur solver")
    if bf16 or bf16_collectives:
        raise NotImplementedError(
            "SolverOption.bf16 is only implemented for the Schur solver "
            "(validate_options refuses it with use_schur=False)")

    Hpp_d = damp_blocks(system.Hpp, region)
    Hll_d = damp_rows_fm(system.Hll, region)
    Minv_c = block_inv(Hpp_d)
    Minv_p = block_inv_fm(Hll_d)

    hpl, hlp = make_coupling_matvecs(
        system.W, Jc, Jp, cam_idx, pt_idx, num_cameras, num_points,
        compute_kind, axis_name, cam_sorted=cam_sorted, plans=plans,
    )

    def h_matvec(x):
        # [Hpp Hpl; Hlp Hll] applied blockwise      [2 psums]
        xc, xp = x
        return (cam_block_matvec(Hpp_d, xc) + hpl(xp),
                hlp(xc) + block_matvec_fm(Hll_d, xp))

    def precond(r):
        rc, rp = r
        return cam_block_matvec(Minv_c, rc), block_matvec_fm(Minv_p, rp)

    (xc, xp), k, rho, r0_ratio, restarts, broken = _pcg_core(
        h_matvec, precond, (system.g_cam, system.g_pt),
        max_iter, tol, refuse_ratio, tol_relative, x0=x0,
        guard=guard, max_restarts=max_restarts)
    return PCGResult(dx_cam=xc, dx_pt=xp, iterations=k, rho=rho,
                     r0_ratio=r0_ratio, breakdowns=restarts, broken=broken)


def schur_pcg_solve(
    system: SchurSystem,
    Jc: jax.Array,
    Jp: jax.Array,
    cam_idx: jax.Array,
    pt_idx: jax.Array,
    region: jax.Array,
    max_iter: int = 100,
    tol: float = 1e-1,
    refuse_ratio: float = 1.0,
    tol_relative: bool = False,
    compute_kind: ComputeKind = ComputeKind.IMPLICIT,
    axis_name: Optional[str] = None,
    mixed_precision: bool = False,
    cam_sorted: bool = False,
    preconditioner: PreconditionerKind = PreconditionerKind.HPP,
    plans: Optional[DualPlans] = None,
    x0: Optional[jax.Array] = None,
    guard: bool = False,
    max_restarts: int = 0,
    precond: PrecondKind = PrecondKind.JACOBI,
    neumann_order: int = 2,
    cluster_plan=None,
    cam_fixed=None,
    smooth_omega: float = 0.0,
    tile_plan=None,
    bf16: bool = False,
    bf16_collectives: bool = False,
    fused_kernels: bool = False,
) -> PCGResult:
    """Solve the damped Schur system for (dx_cam, dx_pt), feature-major.

    Semantics follow the reference (SolverOption defaults common.h:27-33):
    `tol` is the absolute threshold on rho = <r, M^-1 r> (loop exits when
    |rho| < tol, schur_pcg_solver.cu:406-407); `refuse_ratio` is the
    divergence guard — when rho exceeds refuse_ratio * min(rho) the solver
    restores the best iterate and stops (schur_pcg_solver.cu:288-296).
    `region` is the LM trust region; damping multiplies block diagonals by
    (1 + 1/region).

    `x0` ([cd, Nc] rows, original variables) warm-starts the reduced CG
    iteration; `tol` may be a traced scalar (the inexact-LM forcing path
    passes eta_k^2 per LM iteration).

    `precond` selects the preconditioner operator family
    (solver/precond.py): JACOBI (the block diagonal picked by
    `preconditioner`, bitwise the historical solver), NEUMANN
    (`neumann_order` extra S applications per apply), TWO_LEVEL
    (needs the host-planned `cluster_plan` operand —
    ops/segtiles.cached_cluster_plan; `cam_fixed` keeps the coarse
    correction off pinned cameras), or MULTILEVEL (the recursive
    L-level hierarchy; `cluster_plan` is then a DeviceMultiLevelPlan —
    ops/segtiles.cached_multilevel_plan).  `smooth_omega` > 0 smooths
    the level-1 prolongator (smoothed aggregation) for both
    coarse-space kinds.

    `bf16` / `bf16_collectives` (SolverOption.bf16 / .bf16_collectives)
    select the bf16 MXU pipeline: the SAME Jacobi equilibration as
    `mixed_precision` (bf16 needs well-ranged operands either way),
    but the bf16 rows are fed to the products AS bf16 with f32
    accumulation (`_edge_precision`), the block-diagonal preconditioner
    apply runs on a bf16 copy of M⁻¹ with f32 accumulation
    (solver/precond.py), and the collective gate compresses the S·p
    matvec's in-body wire payloads to bf16 — while the reduced RHS,
    the back-substitution and every coarse-space build keep
    full-precision collectives (their hpl/hlp closures are built
    uncompressed below).
    """
    # Retrace sentinel hook (analysis/retrace.py): counts only under an
    # active jax trace — eager calls are not compilations.
    note_trace("solver.schur_pcg", system.g_cam, system.g_pt, Jc, Jp,
               static=static_key(compute_kind, axis_name, mixed_precision,
                                 preconditioner, precond, neumann_order,
                                 smooth_omega, bf16, bf16_collectives,
                                 fused_kernels))
    # Everything before the CG loop (damping, equilibration, Hll^-1,
    # the preconditioner build, the reduced RHS) is one device layer;
    # the closures built here run under whichever scope calls them.
    with jax.named_scope("megba.pcg_setup"):
        num_cameras = system.Hpp.shape[0]
        num_points = system.Hll.shape[1]
        pd = int(round(system.Hll.shape[0] ** 0.5))

        Hpp_d = damp_blocks(system.Hpp, region)
        Hll_d = damp_rows_fm(system.Hll, region)
        g_cam, g_pt = system.g_cam, system.g_pt
        W = system.W

        # Both precision rungs equilibrate + bf16-cast the stored rows; they
        # differ only in WHERE the upcast happens (before vs after the
        # multiply — _edge_precision).
        equil = mixed_precision or bf16
        d_cam = d_pt = None
        if equil:
            # Jacobi (scale-then-cast) equilibration: BA Jacobian columns span
            # ~6 orders of magnitude (rotation vs focal), far beyond bf16's
            # dynamic range.  Solve the symmetrically scaled system
            # (D S D) x~ = D v with D = diag(H)^-1/2 — unit-diagonal, so the
            # bf16-cast coupling operands are well-ranged — and unscale the
            # solution at the end.
            cd = Hpp_d.shape[-1]
            dc = jax.lax.rsqrt(
                jnp.diagonal(Hpp_d, axis1=-2, axis2=-1))  # [Nc, cd]
            Hpp_d = Hpp_d * dc[:, :, None] * dc[:, None, :]
            d_cam = jnp.swapaxes(dc, 0, 1)  # [cd, Nc] rows
            d_pt = jax.lax.rsqrt(jnp.stack(
                [Hll_d[i * (pd + 1)] for i in range(pd)]))  # [pd, Np]
            Hll_d = Hll_d * jnp.stack(
                [d_pt[i] * d_pt[j] for i in range(pd) for j in range(pd)])
            g_cam = g_cam * d_cam
            g_pt = g_pt * d_pt
            bf = jnp.bfloat16
            if plans is not None:
                # Sorted expansions instead of random gathers; Jp's scale
                # rows must be in PT-slot order, like Jp itself.
                dc_e = seg_expand(d_cam, plans.cam, plans.use_kernels,
                                  name="tile_expand_cam_to_edge")
                dp_e_pt = seg_expand(d_pt, plans.pt, plans.use_kernels,
                                     name="tile_expand_pt_to_edge")
                dp_e = plans.to_cam(dp_e_pt) if (
                    compute_kind == ComputeKind.EXPLICIT) else dp_e_pt
            else:
                dc_e = gather_fm(d_cam, cam_idx)  # [cd, nE]
                dp_e = gather_fm(d_pt, pt_idx)  # [pd, nE]
            if compute_kind == ComputeKind.EXPLICIT:
                W = jnp.stack([
                    W[a * pd + b] * dc_e[a] * dp_e[b]
                    for a in range(cd) for b in range(pd)
                ]).astype(bf)
            else:
                od = Jc.shape[0] // cd
                Jc = jnp.stack([
                    Jc[o * cd + a] * dc_e[a]
                    for o in range(od) for a in range(cd)
                ]).astype(bf)
                Jp = jnp.stack([
                    Jp[o * pd + b] * dp_e[b]
                    for o in range(od) for b in range(pd)
                ]).astype(bf)

        Hll_inv = block_inv_fm(Hll_d)

        hpl, hlp = make_coupling_matvecs(
            W, Jc, Jp, cam_idx, pt_idx, num_cameras, num_points,
            compute_kind, axis_name, mixed_precision=mixed_precision,
            cam_sorted=cam_sorted, plans=plans, bf16_ops=bf16,
            fused_kernels=fused_kernels,
        )

        if tile_plan is not None:
            # 2-D mesh: the SINGLE matvec site becomes the fused tiled
            # pipeline with subgroup collectives + double-buffered
            # point-shard rotation (make_matvec_2d).  Everything OUTSIDE
            # the PCG body — the reduced RHS, the warm-start residual
            # priming, the back-substitution, the coarse-space builds —
            # keeps the plain hpl/hlp products above (world psums, one per
            # PCG solve, not per iteration), so the preconditioner family
            # and the guards compose unchanged.
            s_matvec = make_matvec_2d(
                W, Jc, Jp, tile_plan, pt_idx, Hpp_d, Hll_inv,
                num_cameras, num_points, compute_kind, axis_name,
                mixed_precision=mixed_precision, bf16_ops=bf16,
                bf16_collectives=bf16_collectives,
                fused_kernels=fused_kernels)
        else:
            if bf16_collectives and axis_name is not None:
                # Compressed coupling pair for the S·p matvec ONLY: the
                # in-body psums carry bf16 payloads while the reduced RHS /
                # back-substitution products below keep the full-precision
                # hpl/hlp (their psums run once per solve, not per
                # iteration — compressing them buys nothing and costs
                # accuracy exactly where the solution is assembled).
                hpl_c, hlp_c = make_coupling_matvecs(
                    W, Jc, Jp, cam_idx, pt_idx, num_cameras, num_points,
                    compute_kind, axis_name, mixed_precision=mixed_precision,
                    cam_sorted=cam_sorted, plans=plans, bf16_ops=bf16,
                    bf16_collectives=True, fused_kernels=fused_kernels,
                )
            else:
                hpl_c, hlp_c = hpl, hlp

            def s_matvec(p: jax.Array) -> jax.Array:
                # S p = Hpp_d p - Hpl Hll_d^-1 Hlp p     [2 psums]
                t = block_matvec_fm(Hll_inv, hlp_c(p))
                return cam_block_matvec(Hpp_d, p) - hpl_c(t)

        # Preconditioner operator family (solver/precond.py).  The
        # correction/coarse rows are always accumulated in full precision
        # (any bf16 operands are upcast inside the builds); the only
        # precision flag threaded through is the bf16 pipeline's
        # block-diagonal APPLY (bf16 M⁻¹ copy, f32-accumulated einsum —
        # the coarse cycles smooth with it but assemble/solve their coarse
        # systems in f32).  JACOBI reproduces the historical
        # solver bitwise; `precond_fallback` is the enum-coded per-level
        # fallback count (two-level -> block-Jacobi, SCHUR_DIAG block ->
        # Hpp).
        precond_apply, precond_fallback = make_schur_preconditioner(
            precond, preconditioner, Hpp_d, Hll_inv, W, Jc, Jp,
            cam_idx, pt_idx, num_cameras, compute_kind, axis_name,
            cam_sorted, neumann_order=neumann_order, plans=plans,
            cluster_plan=cluster_plan, cam_fixed=cam_fixed,
            s_matvec=s_matvec, smooth_omega=smooth_omega, bf16=bf16,
            fused_kernels=fused_kernels)

        # Reduced RHS v = g_cam - Hpl Hll^-1 g_pt    [1 psum]
        v = g_cam - hpl(block_matvec_fm(Hll_inv, g_pt))

        if x0 is not None and equil:
            # The CG runs in the symmetrically scaled variables x~ = x / d;
            # bring the (original-variable) warm start over.
            x0 = x0 / d_cam

        if bf16 and tol_relative:
            # Attainable-accuracy floor: a bf16-operand operator cannot
            # resolve relative preconditioned-residual energies below
            # ~eps_bf16² — an Eisenstat-Walker eta driven under the floor
            # (eta_min defaults to 1e-6) would spin the inner solve at its
            # noise floor for the full budget.  Clamp the RELATIVE
            # threshold only; an absolute `tol` has no scale to clamp
            # against (the refuse guard handles stagnation there).
            tol = jnp.maximum(jnp.asarray(tol, v.dtype),
                              jnp.asarray(_BF16_TOL_FLOOR, v.dtype))

    x, k, rho, r0_ratio, restarts, broken = _pcg_core(
        s_matvec, precond_apply, v,
        max_iter, tol, refuse_ratio, tol_relative, x0=x0,
        guard=guard, max_restarts=max_restarts, fused=not bf16)

    # Back-substitute the point update       [1 psum]
    with jax.named_scope("megba.pcg_backsub"):
        dx_pt = block_matvec_fm(Hll_inv, g_pt - hlp(x))
        if equil:
            x = x * d_cam  # unscale back to the original variables
            dx_pt = dx_pt * d_pt
    return PCGResult(dx_cam=x, dx_pt=dx_pt, iterations=k, rho=rho,
                     r0_ratio=r0_ratio, breakdowns=restarts, broken=broken,
                     precond_fallback=precond_fallback)
