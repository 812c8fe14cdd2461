"""Block-structured Schur linear system assembly (feature-major).

TPU-native replacement for the reference's Hessian assembly + CSR
machinery: the `makeHSchur` atomicAdd kernels
(reference src/edge/build_linear_system.cu:88-146), the CSR skeleton
builders (reference src/linear_system/schur_LM_linear_system.cpp:20-84)
and the positionContainer relativePosition indexing
(reference src/edge/base_edge.cpp:224-262) all collapse into chunked
scatter-adds of per-edge outer-product ROWS (see core/fm.py for the
feature-major layout rationale):

  Hpp [num_cameras, cd, cd]   block-diagonal camera Hessian (small)
  Hll [pd*pd, num_points]     block-diagonal point Hessian, row form
  g_cam [cd, num_cameras], g_pt [pd, num_points]   gradient -J^T r

The per-edge outer products are never materialised over the full edge
axis: the build scans edge CHUNKS, building each chunk's feature rows
[~F, chunk] in registers/VMEM-sized transients and scatter-adding into
the accumulators — bounding transient HBM to ~100 MB at ANY problem
scale (the edge-major einsum+segment_sum form needs 41 GB at Venice
scale from (8,128) tile padding alone).

The camera-point coupling Hpl is either materialised as per-edge block
rows W [cd*pd, nE] (EXPLICIT — the analog of the reference's Hpl/Hlp
CSR, schur_linear_system.h:22-29) or recomputed from the stored
Jacobians at every matvec (IMPLICIT — the analog of
reference src/solver/implicit_schur_pcg_solver.cu:20-90).  In both modes
Hpl stays shard-local when the edge axis is sharded: only the
block-diagonals and the gradient are psum-reduced, mirroring the
reference's allreduce set (build_linear_system.cu:403-422, where Hpl/Hlp
are deliberately NOT reduced).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from megba_tpu.common import ComputeKind
from megba_tpu.core.fm import chunked_edge_reduce, coupling_rows, slice_fm
from megba_tpu.ops.residuals import apply_sqrt_info
from megba_tpu.ops.segtiles import DualPlans, jtj_grad_reduce

# Hessian contractions always run at full float32: on TPU the default
# bf16 matmul precision would corrupt the normal equations.  bf16 is an
# explicit opt-in for the PCG matvecs only
# (ProblemOption.mixed_precision_pcg).
HI = jax.lax.Precision.HIGHEST


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SchurSystem:
    """The assembled (undamped) normal equations in Schur block form.

    Equivalent of the reference's SchurLMLinearSystem containers
    (include/linear_system/schur_linear_system.h:22-29): csrVal[2]=Hpp,
    csrVal[3]=Hll, g — plus the per-edge W rows in EXPLICIT mode
    (csrVal[0]/csrVal[1]=Hpl/Hlp there).  Undamped; LM damping is applied
    functionally (`damp_blocks` / `core.fm.damp_rows_fm` — the
    reference's in-place processDiag/recoverDiag save-restore dance,
    schur_LM_linear_system.cu:112-185, is unnecessary in functional
    form).  Point-side containers are feature-major rows; the camera side
    is small enough to stay block-batched.
    """

    Hpp: jax.Array  # [Nc, cd, cd], psum-reduced (replicated across shards)
    Hll: jax.Array  # [pd*pd, Np] rows, psum-reduced
    g_cam: jax.Array  # [cd, Nc], psum-reduced
    g_pt: jax.Array  # [pd, Np], psum-reduced
    W: Optional[jax.Array] = None  # [cd*pd, nE_local], shard-local (EXPLICIT)


def weight_system_inputs(
    r: jax.Array,
    Jc: jax.Array,
    Jp: jax.Array,
    cam_idx: jax.Array,
    pt_idx: jax.Array,
    mask: jax.Array,
    sqrt_info: Optional[jax.Array] = None,
    cam_fixed: Optional[jax.Array] = None,
    pt_fixed: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Apply sqrt-information, padding mask and fixed-vertex masks ONCE.

    Feature-major: r [od, nE], Jc [od*cd, nE], Jp [od*pd, nE], mask [nE].
    The returned (r, Jc, Jp) are what both `build_schur_system` and the
    PCG matvecs consume, so masking can never be double-applied.  Covers
    the reference's JMulInfo pre-weighting
    (build_linear_system.cu:148-239) and its gradShape=0 exclusion of
    fixed vertices (base_vertex.h:48-50).  mask is 0/1 so H = J^T J picks
    up mask^2 = mask and g = -J^T r picks up mask^2 as well — padding
    edges contribute exactly nothing.
    """
    r, Jc, Jp = apply_sqrt_info(r, Jc, Jp, sqrt_info)
    m = mask[None, :]
    r = r * m
    Jc = Jc * m
    Jp = Jp * m
    if cam_fixed is not None:
        # zeros_like: the weak literal 0.0 would ride in as a f64
        # constant tensor in f32 programs (dtype-census leak).
        Jc = jnp.where(cam_fixed[cam_idx][None, :], jnp.zeros_like(Jc), Jc)
    if pt_fixed is not None:
        Jp = jnp.where(pt_fixed[pt_idx][None, :], jnp.zeros_like(Jp), Jp)
    return r, Jc, Jp


def _outer_rows(J: jax.Array, od: int, d: int) -> jax.Array:
    """[od*d, n] Jacobian rows -> [d*d, n] rows of J^T J (sum over od)."""
    return jnp.stack([
        sum(J[o * d + a] * J[o * d + b] for o in range(od))
        for a in range(d) for b in range(d)
    ])


def _grad_rows(J: jax.Array, r: jax.Array, od: int, d: int) -> jax.Array:
    """[od*d, n] Jacobian rows, [od, n] residual -> [d, n] rows of -J^T r."""
    return jnp.stack([
        -sum(J[o * d + a] * r[o] for o in range(od)) for a in range(d)
    ])


# named_scope: labels every assembly op in profiler traces
# (TensorBoard/Perfetto via utils.timing.trace_profile) at zero runtime
# cost — the Schur build is a hot phase worth finding at a glance.
@jax.named_scope("megba.schur_build")
def build_schur_system(
    r: jax.Array,
    Jc: jax.Array,
    Jp: jax.Array,
    cam_idx: jax.Array,
    pt_idx: jax.Array,
    num_cameras: int,
    num_points: int,
    compute_kind: ComputeKind = ComputeKind.IMPLICIT,
    axis_name: Optional[str] = None,
    cam_fixed: Optional[jax.Array] = None,
    pt_fixed: Optional[jax.Array] = None,
    cam_sorted: bool = False,
    plans: Optional[DualPlans] = None,
) -> SchurSystem:
    """Assemble the Schur-form normal equations from per-edge Jacobians.

    Feature-major inputs (already weighted by `weight_system_inputs`):
    r [od, nE], Jc [od*cd, nE], Jp [od*pd, nE]; cam_idx/pt_idx [nE] int32.

    `cam_sorted=True` asserts edges are ordered by cam_idx (BAL files
    are; BaseProblem sorts at lowering) — camera-side scatters then run
    as sorted segment reductions.

    `plans` (ops/segtiles.DualPlans) selects the scatter-free tiled
    build: `Jc`/`r` are in cam-plan slot order, `Jp` is in PT-plan slot
    order, and both block-diagonals come from the fused
    `jtj_grad_reduce` kernel (the reference's makeHSchur / makeHppHllSchur
    fusion, build_linear_system.cu:88-146 /
    build_implicit_linear_system.cu:65-111, re-expressed as one-hot MXU
    matmuls).  Without plans, the chunked scatter-add path runs (CPU /
    f64 / sharded mesh).

    `axis_name`: mesh axis (or, on the 2-D camera x edge mesh, the
    (EDGE_AXIS, CAM_AXIS) tuple — `jax.lax.psum` over the tuple reduces
    over the whole world) to psum over when the edge axis is sharded
    (the reference's ncclAllReduce of Hpp/Hll/g,
    build_linear_system.cu:403-422); None on a single device.  The
    build runs once per LINEARISATION, so these stay whole-world
    reductions on both mesh shapes — only the per-PCG-iteration matvec
    pays for subgroup scoping (solver/pcg.make_matvec_2d).
    `cam_fixed` / `pt_fixed`: optional bool masks; fixed vertices get an
    identity Hessian block and zero gradient so their update is exactly
    zero.
    """
    od = r.shape[0]
    cd = Jc.shape[0] // od
    pd = Jp.shape[0] // od
    nE = r.shape[1]
    dtype = r.dtype

    if plans is not None:
        if dtype != jnp.float32:
            # The kernels accumulate in float32; silently downgrading a
            # float64 build would corrupt the double-precision pipeline.
            raise ValueError(
                f"plans requires float32 inputs, got {dtype}; "
                "use the XLA path (plans=None) for other dtypes")
        hpp_rows, g_cam = jtj_grad_reduce(
            Jc, r, plans.cam, plans.use_kernels, name="jtj_grad_reduce_cam")
        r_pt = plans.to_pt(r)
        hll_acc = jnp.concatenate(jtj_grad_reduce(
            Jp, r_pt, plans.pt, plans.use_kernels, name="jtj_grad_reduce_pt"))
    else:
        # Chunked scatter-add build: per chunk, form the outer-product
        # rows [d*d + d, chunk] and accumulate — the race-free functional
        # form of the reference's atomicAdd makeHpp / makeHll
        # (build_linear_system.cu:116-134) with bounded transients.
        def body(start, size, accs):
            hpp_a, hll_a = accs
            jp = slice_fm(Jp, start, size)
            rr = slice_fm(r, start, size)
            pi = jax.lax.dynamic_slice_in_dim(pt_idx, start, size)
            jc = slice_fm(Jc, start, size)
            ci = jax.lax.dynamic_slice_in_dim(cam_idx, start, size)
            cam_feat = jnp.concatenate(
                [_outer_rows(jc, od, cd), _grad_rows(jc, rr, od, cd)])
            hpp_a = hpp_a.at[:, ci].add(
                cam_feat, indices_are_sorted=cam_sorted, mode="drop")
            pt_feat = jnp.concatenate(
                [_outer_rows(jp, od, pd), _grad_rows(jp, rr, od, pd)])
            hll_a = hll_a.at[:, pi].add(pt_feat, mode="drop")
            return hpp_a, hll_a

        hpp_init = jnp.zeros((cd * cd + cd, num_cameras), dtype)
        hll_init = jnp.zeros((pd * pd + pd, num_points), dtype)
        hpp_acc, hll_acc = chunked_edge_reduce(
            nE, (hpp_init, hll_init), body)
        hpp_rows = hpp_acc[: cd * cd]
        g_cam = hpp_acc[cd * cd:]
    Hll = hll_acc[: pd * pd]
    g_pt = hll_acc[pd * pd:]

    if axis_name is not None:
        with jax.named_scope("megba.collective"):
            hpp_rows, g_cam, Hll, g_pt = jax.lax.psum(
                (hpp_rows, g_cam, Hll, g_pt), axis_name)

    # Camera blocks to batched [Nc, cd, cd] (small; dense-block ops and
    # the 9x9 Cholesky inverse want this form).
    Hpp = jnp.moveaxis(hpp_rows.reshape(cd, cd, num_cameras), -1, 0)

    # Fixed vertices: identity block + zero gradient pins delta to zero.
    eye_c = jnp.eye(cd, dtype=dtype)
    eye_p_rows = jnp.asarray(
        [1.0 if i % (pd + 1) == 0 else 0.0 for i in range(pd * pd)], dtype)
    if cam_fixed is not None:
        Hpp = jnp.where(cam_fixed[:, None, None], eye_c, Hpp)
        # zeros_like, not the literal 0.0: a weak f64 scalar constant
        # would materialise as tensor<f64> in f32 programs (the dtype
        # census flags it — same class of leak as the ops/geo.py ones).
        g_cam = jnp.where(cam_fixed[None, :], jnp.zeros_like(g_cam), g_cam)
    if pt_fixed is not None:
        Hll = jnp.where(pt_fixed[None, :], eye_p_rows[:, None], Hll)
        g_pt = jnp.where(pt_fixed[None, :], jnp.zeros_like(g_pt), g_pt)

    # Edge-less vertices (possible in filtered real datasets) would leave
    # a zero block that stays singular through multiplicative damping and
    # NaN-poisons the inverse.  J^T J is PSD, so a zero trace identifies
    # exactly the empty blocks; give them an identity (their gradient is
    # already zero, so their update is exactly zero).
    empty_c = jnp.trace(Hpp, axis1=-2, axis2=-1) == 0.0
    Hpp = jnp.where(empty_c[:, None, None], eye_c, Hpp)
    tr_rows = [i for i in range(pd * pd) if i % (pd + 1) == 0]
    empty_p = sum(Hll[i] for i in tr_rows) == 0.0
    Hll = jnp.where(empty_p[None, :], eye_p_rows[:, None], Hll)

    W = None
    if compute_kind == ComputeKind.EXPLICIT:
        # Shard-local coupling rows (NOT reduced — the distributed matvec
        # psums the product instead, mirroring the reference's
        # beta=1/worldSize trick + product allreduce,
        # schur_pcg_solver.cu:478-509).  W lives in cam-slot order; under
        # plans, Jp is pt-ordered and must be brought over first.
        Jp_cam = plans.to_cam(Jp) if plans is not None else Jp
        W = coupling_rows(Jc, Jp_cam, od)
    return SchurSystem(Hpp=Hpp, Hll=Hll, g_cam=g_cam, g_pt=g_pt, W=W)


def coupling_row_provider(
    W: Optional[jax.Array],
    Jc: Optional[jax.Array],
    Jp: Optional[jax.Array],
    od: int,
    compute_kind: ComputeKind,
    dtype,
    plans: Optional[DualPlans] = None,
):
    """Chunk accessor for the per-edge coupling block rows W_e = Jc_eᵀJp_e.

    Returns `rows(start, size) -> [cd*pd, size]` in the CAM edge order
    and the solve dtype, reading the materialised `W` rows in EXPLICIT
    mode and recomputing from the stored Jacobians in IMPLICIT mode
    (upcast from bf16 under mixed precision) — the ONE definition of
    "give me this edge chunk's coupling blocks" shared by the
    Schur-diagonal preconditioner build and the two-level coarse
    operator assembly (solver/precond.py), so the two consumers can
    never disagree about layout or precision.  Under `plans`, `Jp` is
    carried PT-ordered (algo/lm.py) and is brought to cam order once
    here.
    """
    if compute_kind == ComputeKind.EXPLICIT:
        def rows(start, size):
            return slice_fm(W, start, size).astype(dtype)

        return rows
    if plans is not None and Jp is not None:
        Jp = plans.to_cam(Jp)

    def rows(start, size):
        jc = slice_fm(Jc, start, size).astype(dtype)
        jp = slice_fm(Jp, start, size).astype(dtype)
        return coupling_rows(jc, jp, od)

    return rows


def coupling_row_gather(
    W: Optional[jax.Array],
    Jc: Optional[jax.Array],
    Jp: Optional[jax.Array],
    od: int,
    compute_kind: ComputeKind,
    dtype,
    plans: Optional[DualPlans] = None,
):
    """`coupling_row_provider`'s random-access sibling: returns
    `rows_at(idx) -> [cd*pd, len(idx)]` gathering the coupling block
    rows at arbitrary (quasi-sorted) edge indices instead of contiguous
    chunks — the access pattern of the two-level coarse build's
    ec-pair stream, where each edge appears once per cluster of its
    point (solver/precond.py)."""
    from megba_tpu.core.fm import gather_fm

    if compute_kind == ComputeKind.EXPLICIT:
        def rows_at(idx):
            return gather_fm(W, idx).astype(dtype)

        return rows_at
    if plans is not None and Jp is not None:
        Jp = plans.to_cam(Jp)

    def rows_at(idx):
        jc = gather_fm(Jc, idx).astype(dtype)
        jp = gather_fm(Jp, idx).astype(dtype)
        return coupling_rows(jc, jp, od)

    return rows_at


def damp_blocks(H: jax.Array, region: jax.Array) -> jax.Array:
    """LM damping on batched [N, d, d] blocks: diagonal scales by
    (1 + 1/region).

    The multiplicative damping of the reference's
    extractOldAndApplyNewDiag kernel (schur_LM_linear_system.cu:112-160);
    being functional, there is nothing to save or recover on reject.
    Row-form point blocks use `core.fm.damp_rows_fm`.
    """
    d = H.shape[-1]
    eye = jnp.eye(d, dtype=H.dtype)
    factor = 1.0 + eye / region
    return H * factor
