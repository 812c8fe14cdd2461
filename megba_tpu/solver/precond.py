"""Pluggable Schur-complement preconditioners.

The preconditioner is a pluggable operator family
(`SolverOption.precond`, common.PrecondKind) whose matrix-free members
all run inside the single fused PCG program:

JACOBI — the extracted baseline: apply the inverted block diagonal
  (damped Hpp, or the true Schur diagonal under
  `PreconditionerKind.SCHUR_DIAG`).  Bitwise identical to the
  pre-subsystem solver.

NEUMANN — truncated Neumann/power-series expansion of S⁻¹ around the
  block diagonal D:  M⁻¹ = Σ_{i=0..k} (I − D⁻¹S)^i D⁻¹, applied by
  Horner recursion (z ← z + D⁻¹(r − S z), k times).  Symmetric by
  construction (each term E^i D⁻¹ is — D and S are), positive definite
  whenever the D-preconditioned spectrum stays in (0, 2) (block-Jacobi
  on damped BA systems clusters it near 1).  Each apply costs k extra
  S applications INSIDE the PCG while body — 2k extra all-reduces per
  iteration when sharded — so it trades communication for iterations
  and must be judged on wall-clock, never iteration counts alone.

TWO_LEVEL — a BA-shaped two-level (multigrid-flavoured) scheme:
  cameras are aggregated into O(sqrt(Nc)) clusters by a greedy
  co-observation-weighted host plan (ops/segtiles.build_cluster_plan,
  cached behind the plan-fingerprint LRU), R is the piecewise-constant
  aggregation over camera blocks (fixed cameras masked out), and the
  coarse operator is the EXACT Galerkin projection of the damped Schur
  complement

      A_c = R S_d Rᵀ = R G,      G = S_d Rᵀ,
      G[n, (J,b)] = (Hpp_d)_n R[n,J] − Σ_{e: cam(e)=n} W_e Hll⁻¹ V_Jᵀ,
      V_{p,I} = Σ_{e: pt(e)=p, cluster(cam(e))=I} W_e,

  assembled once per PCG solve from already-materialised quantities:
  the damped camera blocks, Hll⁻¹, and the per-edge coupling rows W_e
  (read in EXPLICIT mode, recomputed chunk-wise from the stored
  Jacobians in IMPLICIT mode — linear_system.coupling_row_provider /
  coupling_row_gather).  No black-box S applications, no new
  collective kinds: ONE psum each for V and G when sharded, both
  OUTSIDE the PCG while body.  The coarse system (a few hundred
  unknowns) is factored by a small replicated spectrally-FILTERED
  eigendecomposition (solver/dense.dense_filtered_factor — see
  _COARSE_EIG_FLOOR for why near-null modes are dropped, not inverted)
  and the apply is the SYMMETRIZED MULTIPLICATIVE two-level cycle
  (coarse correction + block-Jacobi smoothing + coarse re-correction —
  V(0,1)-cycle with exact-on-the-kept-spectrum coarse solve):

      M⁻¹ = Rᵀ A_c⁻¹ R + Pᵀ D⁻¹ P,     P = I − S_d Rᵀ A_c⁻¹ R

  Because P's S application only ever hits vectors in range(Rᵀ), the
  materialised G = S_d Rᵀ turns both "S applies" of the cycle into
  tiny replicated [cd·Nc, C·cd] matmuls — the per-apply work is two
  coarse triangular solves, two G contractions and one block-diagonal
  smooth: ZERO collectives inside the while body (the
  `ba_twolevel_w2_f32` canonical program pins exactly 2 all-reduces
  per S·p there).  Unlike the ADDITIVE combination D⁻¹ + RᵀA_c⁻¹R
  (which re-widens the spectrum wherever coarse and fine ranges
  overlap — measured 1.5x MORE iterations on a venice-shaped CPU
  scene), the multiplicative cycle leaves coarse modes with eigenvalue
  exactly 1.
  M⁻¹ is SPD: both terms are PSD and their kernels are disjoint
  (P r = r on ker(R), where D⁻¹ is PD).

MULTILEVEL — TWO_LEVEL generalized to a recursive L-level hierarchy:
  the level-1 coarse space is the same co-observation aggregation, and
  every coarser level re-aggregates the previous level's cluster graph
  (host-planned once — ops/segtiles.build_multilevel_plan).  Level 1's
  Galerkin operator/coupling are assembled exactly as TWO_LEVEL's;
  every deeper level's A_{l+1} = R_l A_l R_lᵀ is a tiny replicated
  dense contraction.  The coarse solve is a recursive SYMMETRIC V(1,1)
  cycle (damped block-Jacobi pre-smooth, coarse correction on the true
  residual, post-smooth; smoother weight 1/λmax(D⁻¹A) by power
  iteration so the cycle is SPD on any spectrum), with the dense
  filtered pseudo-inverse ONLY at the coarsest level.  Zero in-body
  collectives, pinned by `ba_multilevel_w2_f32`.

Both coarse-space kinds accept SMOOTHED-AGGREGATION prolongators
(`smooth_omega` > 0): Π = Rᵀ − ω D⁻¹ S_d Rᵀ — the expander-robust
variant.  The already-materialised G₀ = S_d Rᵀ makes the smoothing
correction Y = D⁻¹G₀ one blockwise product; the exact smoothed
Galerkin costs one extra column-blocked S_d·Y pass per build
(_smooth_correction), still outside the PCG while body.

Fallback ladder (extends PR 5's Cholesky-NaN semantics one level up):
a non-finite coarse operator TRUNCATES the cycle at its level —
level 1 degrades to plain block-Jacobi (the cycle becomes EXACTLY the
base apply), a deeper level only drops the sub-hierarchy below it —
and, independently, per camera block, an indefinite SCHUR_DIAG block
falls back to the Hpp preconditioner.  Every level is COUNTED, not
silent: `PCGResult.precond_fallback` carries an enum-coded int32
(low 16 bits block count, high bits a per-level bit-field —
encode/decode below) into `SolveTrace`/`SolveReport`.

Measured on the CPU (venice-10% synthetic scene, inexact-LM config):
NEUMANN k=1 cut total PCG iterations 40% (70 -> 42) at 9e-8 relative
cost gap.  TWO_LEVEL is dense-verified exact and cuts the
preconditioned condition number 54 -> 4.3 on small systems, but the
synthetic scene's camera graph is an expander ((base + j*stride) mod
Nc observation assignment — no cluster structure), so its coarse space
captures nothing there and block-Jacobi stays the better default on
it; it targets spatially-local real scenes.  See ARCHITECTURE.md
"Preconditioner hierarchy".
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from megba_tpu.common import ComputeKind, PrecondKind, PreconditionerKind
from megba_tpu.core.fm import chunked_edge_reduce, gather_fm
from megba_tpu.linear_system.builder import (
    coupling_row_gather,
    coupling_row_provider,
)
from megba_tpu.solver.dense import dense_filtered_factor, dense_filtered_solve

HI = jax.lax.Precision.HIGHEST

# Per-pair-chunk transient bound for the coarse correction contraction:
# [cd*cd, chunk] rows (~21 MB f32 at the default — same class as the
# Hessian build chunks).
_PAIR_CHUNK = 65_536

# Relative eigenvalue floor of the filtered coarse solve
# (dense.dense_filtered_factor).  Two jobs: (1) eigenvalues under
# ~1e-6·lambda_max are below the f32 assembly noise of A_c; (2) under
# weak LM damping (trust region >= ~1e4 — where the venice trajectory
# spends most accepted iterations) the gauge-like near-null modes of S
# survive into A_c, and INVERTING them amplifies directions the Krylov
# iteration never needed to resolve — measured: unfiltered coarse
# solves cost 66-78 PCG iters/LM vs block-Jacobi's flat ~43 at region
# 1e5-3e5 on a venice-3% CPU scene, flipping the two-level win into a
# loss.  Filtered, those modes fall through to the smoother, which
# treats them exactly as block-Jacobi always has.
_COARSE_EIG_FLOOR = 1e-5

# --------------------------------------------------------------------------
# Per-level fallback encoding (SolveTrace / SolveReport observable)
# --------------------------------------------------------------------------
#
# `precond_fallback` is ONE int32 so the trace layout is unchanged; the
# ladder levels ride fixed radixes:
#   low  16 bits — BLOCK level: camera blocks whose SCHUR_DIAG Cholesky
#                  went NaN and fell back to the Hpp preconditioner;
#   high bits    — COARSE levels, a BIT-FIELD: bit (16 + l - 1) set
#                  when hierarchy coarse level l (1-based; TWO_LEVEL
#                  has exactly level 1) was non-finite and the cycle
#                  truncated there.  TWO_LEVEL's historical encoding —
#                  high half 0/1 — is exactly the 1-coarse-level case
#                  of this scheme, so old traces decode unchanged.

FALLBACK_BLOCK_RADIX = 1 << 16
# int32 sign bit keeps the bit-field at <= 15 coarse levels
# (common.validate_options caps SolverOption.max_levels accordingly).
FALLBACK_MAX_COARSE_LEVELS = 15


def encode_precond_fallback(block_count, coarse_bits=0):
    """Pack the block count + coarse-level bit-field into one int32.

    `coarse_bits` is the per-level bit-field (bit l-1 = coarse level l
    degraded); for a two-level scheme it is simply 0/1."""
    block = jnp.minimum(jnp.asarray(block_count, jnp.int32),
                        FALLBACK_BLOCK_RADIX - 1)
    return (jnp.asarray(coarse_bits, jnp.int32)
            * FALLBACK_BLOCK_RADIX + block)


def decode_precond_fallback(code) -> dict:
    """Unpack a trace code into {'block': n, 'coarse': bits} (host ints).

    `coarse` is the raw per-level bit-field; for two-level traces it is
    0/1 (the historical meaning, unchanged).  Use
    `decode_precond_fallback_levels` for the per-level view."""
    c = int(code)
    return {"block": c % FALLBACK_BLOCK_RADIX,
            "coarse": c // FALLBACK_BLOCK_RADIX}


def decode_precond_fallback_levels(code) -> list:
    """Per-coarse-level degrade flags [level 1, level 2, ...] of one
    trace code — trailing healthy levels are trimmed, so a two-level
    code decodes to [] (healthy) or [True]."""
    bits = int(code) // FALLBACK_BLOCK_RADIX
    out = []
    level = 0
    while bits and level < FALLBACK_MAX_COARSE_LEVELS:
        out.append(bool(bits & 1))
        bits >>= 1
        level += 1
    return out


# --------------------------------------------------------------------------
# Block-diagonal bases (the extracted JACOBI baseline)
# --------------------------------------------------------------------------


def cam_block_matvec(H: jax.Array, x: jax.Array) -> jax.Array:
    """[Nc, d, d] camera blocks times [d, Nc] rows -> [d, Nc] rows."""
    return jnp.einsum("nij,jn->in", H, x, precision=HI)


def cam_block_matvec_bf16(H_bf16: jax.Array, x: jax.Array) -> jax.Array:
    """The bf16-MXU-pipeline block apply: bf16 blocks x bf16 rows with
    f32 accumulation.

    `H_bf16` is a bfloat16 copy of the (equilibrated, unit-diagonal —
    well-ranged by construction) inverted block diagonal; `x` is the
    f32 residual, downcast at the operand boundary.  The contraction
    dtype is forced to float32 via `preferred_element_type` — on TPU
    this is EXACTLY the native MXU contract (bf16 operands, f32
    accumulator); default precision, not HIGHEST: a multi-pass
    bf16_3x decomposition would re-spend the bandwidth the bf16
    storage just saved.  Returns f32 rows.
    """
    return jnp.einsum("nij,jn->in", H_bf16, x.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def block_inv(H: jax.Array) -> jax.Array:
    """Batched inverse of SPD camera blocks [N, d, d] via Cholesky.

    The analog of the reference's cublasGmatinvBatched calls
    (schur_pcg_solver.cu:60-97); stable on the damped SPD blocks.
    Point blocks use the row-form closed-form `core.fm.block_inv_fm`.
    """
    d = H.shape[-1]
    chol = jnp.linalg.cholesky(H)
    eye = jnp.broadcast_to(jnp.eye(d, dtype=H.dtype), H.shape)
    inv_l = jax.scipy.linalg.solve_triangular(chol, eye, lower=True)
    return jnp.einsum("nki,nkj->nij", inv_l, inv_l, precision=HI)


@jax.named_scope("megba.schur_diag_precond")
def _schur_diag_precond(
    Hpp_d, Hll_inv, W, Jc, Jp, cam_idx, pt_idx, num_cameras,
    compute_kind, axis_name, cam_sorted, plans=None,
):
    """True Schur block diagonal: Hpp_c - sum_e W_e Hll^-1 W_e^T.

    Chunked over edges (like the Hessian build) so the [cd*cd, chunk]
    correction rows never materialise at full edge scale — the round-1
    [nE, 9, 9] transient that made this preconditioner unusable at
    Final scale is gone.
    """
    cd = Hpp_d.shape[-1]
    pd = int(round(Hll_inv.shape[0] ** 0.5))
    dtype = Hpp_d.dtype
    nE = cam_idx.shape[0]
    od = None if Jc is None else Jc.shape[0] // cd
    rows_of = coupling_row_provider(
        W, Jc, Jp, 0 if od is None else od, compute_kind, dtype,
        plans=plans)

    def body(start, size, accs):
        (corr_a,) = accs
        ci = jax.lax.dynamic_slice_in_dim(cam_idx, start, size)
        pi = jax.lax.dynamic_slice_in_dim(pt_idx, start, size)
        hinv = gather_fm(Hll_inv, pi)  # [pd*pd, size]
        w = rows_of(start, size)  # [cd*pd, size]
        # t[a, q] = sum_p w[a, p] hinv[p, q]
        t = [sum(w[a * pd + p] * hinv[p * pd + q] for p in range(pd))
             for a in range(cd) for q in range(pd)]
        corr = jnp.stack([
            sum(t[a * pd + q] * w[b * pd + q] for q in range(pd))
            for a in range(cd) for b in range(cd)
        ])
        return (corr_a.at[:, ci].add(
            corr, indices_are_sorted=cam_sorted, mode="drop"),)

    (corr_rows,) = chunked_edge_reduce(
        nE, (jnp.zeros((cd * cd, num_cameras), dtype),), body)
    if axis_name is not None:
        with jax.named_scope("megba.collective"):
            corr_rows = jax.lax.psum(corr_rows, axis_name)
    corr = jnp.moveaxis(corr_rows.reshape(cd, cd, num_cameras), -1, 0)
    # In exact arithmetic Hpp_d - corr is SPD (a principal block of S),
    # but rounding (especially equilibrated bf16 operands) can push a
    # weakly-determined camera block indefinite -> Cholesky NaN.  Fall
    # back to the Hpp preconditioner for exactly those blocks instead of
    # letting NaN masquerade as convergence.  The fallback is COUNTED,
    # not silent: the block count rides PCGResult.precond_fallback into
    # the SolveTrace so an indefinite drift shows up in telemetry.
    minv_hpp = block_inv(Hpp_d)
    minv_sd = block_inv(Hpp_d - corr)
    bad = ~jnp.all(jnp.isfinite(minv_sd), axis=(-2, -1), keepdims=True)
    return jnp.where(bad, minv_hpp, minv_sd), jnp.sum(bad).astype(jnp.int32)


# --------------------------------------------------------------------------
# Two-level coarse operator (Galerkin R S_d Rᵀ from materialised blocks)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class TwoLevelCoarse:
    """Assembled coarse-space state of one two-level preconditioner.

    `coarse_matrix` [C*cd, C*cd] is the exact Galerkin A_c = R S_d Rᵀ
    (cluster-major unknown ordering: coarse dof (I, a) -> I*cd + a);
    `eig_q`/`eig_inv` its spectrally-filtered pseudo-inverse factor
    (dense.dense_filtered_factor — see _COARSE_EIG_FLOOR for why the
    near-null modes are dropped rather than inverted), `ok` the health
    flag the fallback ladder keys on, `restrict_sel` the [C, Nc]
    fixed-masked aggregation matrix (R at scalar granularity), `G` the
    materialised coarse coupling S_d Rᵀ as [cd, Nc, C, cd] (fine dof
    (a, n) by coarse dof (J, b)).  Exposed as a dataclass so the
    dense-parity property tests can compare `coarse_matrix`/`G`
    against explicitly projected dense operators.
    """

    coarse_matrix: jax.Array
    eig_q: jax.Array  # [C*cd, C*cd] eigenvectors
    eig_inv: jax.Array  # [C*cd] filtered inverse eigenvalues
    ok: jax.Array  # traced bool: coarse factor finite
    restrict_sel: jax.Array  # [C, Nc]
    cluster: jax.Array  # [Nc] int32
    G: jax.Array  # [cd, Nc, C, cd] = S_d Π (Π = prolongator; plain Rᵀ)
    # Smoothed aggregation (smooth_omega > 0): the prolongator becomes
    # Π = Rᵀ − ω Y with Y = D⁻¹ S_d Rᵀ the damped-Jacobi smoothing
    # correction ([cd, Nc, C, cd], fine dof by coarse dof); G and
    # coarse_matrix above are then the SMOOTHED coupling S_d Π and
    # Galerkin Πᵀ S_d Π.  omega == 0 leaves Y None and every field
    # bitwise the PR 7 plain-aggregation state.
    omega: float = 0.0
    Y: Optional[jax.Array] = None


def _smooth_correction(
    Hpp_d, Hll_inv, rows_of, cam_idx, pt_idx, Y, axis_name,
):
    """Z = S_d · Y for the prolongator-correction block columns.

    `Y` [cd, Nc, C, cd] spans the smoothed prolongator's correction
    range; the smoothed Galerkin/coupling need S_d applied to every
    one of its C·cd columns.  Hpp_d·Y is blockwise; the coupling half
    −Hpl Hll⁻¹ Hlp Y runs the two edge-scale passes chunked over BOTH
    edges (bounded per-chunk rows, like every other build) and coarse
    columns (the [pd, Np, mc] incidence transient is the big one — mc
    is capped so it stays ~128 MB f32 at venice scale).  Sharded: one
    psum per column block for the point-incidence sums + one final
    psum for the camera rows — all once per PCG solve, OUTSIDE the PCG
    while body, the collective kind the solver already emits.
    """
    cd = Hpp_d.shape[-1]
    pd = int(round(Hll_inv.shape[0] ** 0.5))
    num_cameras = Hpp_d.shape[0]
    Np = Hll_inv.shape[1]
    dtype = Hpp_d.dtype
    nE = cam_idx.shape[0]
    C = Y.shape[2]
    m = C * cd
    Ym = Y.reshape(cd, num_cameras, m)
    hinv = Hll_inv.reshape(pd, pd, Np)
    mc_cap = max(cd, int(32_000_000 // max(pd * Np, 1)))
    edge_target = max(4096, _PAIR_CHUNK // max(1, min(m, mc_cap) // cd))
    z_cols = []
    for m0 in range(0, m, mc_cap):
        m1 = min(m0 + mc_cap, m)
        mc = m1 - m0
        Yc = jax.lax.slice_in_dim(Ym, m0, m1, axis=2)  # [cd, Nc, mc]

        def ubody(start, size, accs):
            (u_a,) = accs
            ci = jax.lax.dynamic_slice_in_dim(cam_idx, start, size)
            pi = jax.lax.dynamic_slice_in_dim(pt_idx, start, size)
            w = rows_of(start, size)  # [cd*pd, size]
            yg = jnp.take(Yc, ci, axis=1, mode="clip")  # [cd, size, mc]
            rows = jnp.stack([
                sum(w[a * pd + q][:, None] * yg[a] for a in range(cd))
                for q in range(pd)
            ])  # [pd, size, mc] = W_eᵀ Y[cam(e)]
            return (u_a.at[:, pi, :].add(rows, mode="drop"),)

        (U,) = chunked_edge_reduce(
            nE, (jnp.zeros((pd, Np, mc), dtype),), ubody,
            target=edge_target)
        if axis_name is not None:
            with jax.named_scope("megba.collective"):
                U = jax.lax.psum(U, axis_name)
        T = jnp.einsum("qsp,spm->qpm", hinv, U,
                       precision=HI)  # Hll⁻¹ · (Hlp Y)

        def zbody(start, size, accs):
            (z_a,) = accs
            ci = jax.lax.dynamic_slice_in_dim(cam_idx, start, size)
            pi = jax.lax.dynamic_slice_in_dim(pt_idx, start, size)
            w = rows_of(start, size)
            tg = jnp.take(T, pi, axis=1, mode="clip")  # [pd, size, mc]
            rows = jnp.stack([
                sum(w[a * pd + q][:, None] * tg[q] for q in range(pd))
                for a in range(cd)
            ])  # [cd, size, mc] = W_e · (Hll⁻¹ Hlp Y)[pt(e)]
            return (z_a.at[:, ci, :].add(rows, mode="drop"),)

        (Zb,) = chunked_edge_reduce(
            nE, (jnp.zeros((cd, num_cameras, mc), dtype),), zbody,
            target=edge_target)
        z_cols.append(Zb)
    Zcoup = jnp.concatenate(z_cols, axis=2) if len(z_cols) > 1 else z_cols[0]
    if axis_name is not None:
        with jax.named_scope("megba.collective"):
            Zcoup = jax.lax.psum(Zcoup, axis_name)
    Z1 = jnp.einsum("nac,cnJb->anJb", Hpp_d, Y, precision=HI)
    return Z1 - Zcoup.reshape(cd, num_cameras, C, cd)


@jax.named_scope("megba.precond_coarse_build")
def build_two_level_coarse(
    Hpp_d: jax.Array,
    Hll_inv: jax.Array,
    W: Optional[jax.Array],
    Jc: Optional[jax.Array],
    Jp: Optional[jax.Array],
    cluster_plan,
    compute_kind: ComputeKind,
    axis_name: Optional[str] = None,
    cam_fixed: Optional[jax.Array] = None,
    plans=None,
    smooth_omega: float = 0.0,
    Minv: Optional[jax.Array] = None,
    cam_idx: Optional[jax.Array] = None,
    pt_idx: Optional[jax.Array] = None,
    factor: bool = True,
) -> TwoLevelCoarse:
    """Assemble + factor G = S_d Π and A_c = Πᵀ S_d Π.

    Π is the prolongator: the piecewise-constant aggregation Rᵀ when
    `smooth_omega == 0` (the PR 7 operator, bitwise), or the
    SMOOTHED-AGGREGATION Π = Rᵀ − ω D⁻¹ S_d Rᵀ = Rᵀ − ω Y otherwise —
    the already-materialised plain coupling G₀ = S_d Rᵀ makes Y one
    blockwise product, and the exact smoothed Galerkin/coupling
        G = S_d Π = G₀ − ω Z,      A_c = Πᵀ G = R G − ω Yᵀ G,
    cost one extra column-blocked S_d·Y pass (`_smooth_correction`)
    per build.  `Minv` is the smoothing block diagonal D⁻¹ (defaults
    to block_inv(Hpp_d)); `cam_idx`/`pt_idx` (this call's edge streams)
    are required only when smoothing.

    Pure gathers/scatter-adds over the host-planned index arrays
    (ops/segtiles.ClusterPlan) + one small dense eigen-factor; when the
    edge axis is sharded the per-shard V rows are psum-combined BEFORE
    the ec-pair contraction (cross-shard edges of one point are why —
    W_e Hll⁻¹ (ΣV)ᵀ needs the globally-summed V) and the per-shard G
    contributions are psum-combined after it.  Two all-reduces per
    BUILD (once per PCG solve) unsmoothed — plus two per smoothing
    column block — ALL outside the PCG while body, all the collective
    kind the solver already emits.  `factor=False` skips the coarse
    eigendecomposition (the MULTILEVEL hierarchy factors only its
    coarsest level); `ok` then reports finiteness of A_c alone.
    """
    cd = Hpp_d.shape[-1]
    pd = int(round(Hll_inv.shape[0] ** 0.5))
    dtype = Hpp_d.dtype
    num_cameras = Hpp_d.shape[0]
    C = cluster_plan.num_clusters
    n_pc = cluster_plan.n_pc
    od = None if Jc is None else Jc.shape[0] // cd
    rows_of = coupling_row_provider(
        W, Jc, Jp, 0 if od is None else od, compute_kind, dtype,
        plans=plans)
    rows_at = coupling_row_gather(
        W, Jc, Jp, 0 if od is None else od, compute_kind, dtype,
        plans=plans)
    n_edges = cluster_plan.pc_slot.shape[0]

    # V rows [cd*pd, n_pc]: per-(point, cluster) aggregated coupling.
    # Padding / masked edges carry the inert slot n_pc -> dropped (their
    # rows are zero anyway — the Jacobians are mask-multiplied).
    def vbody(start, size, accs):
        (v_a,) = accs
        sl = jax.lax.dynamic_slice_in_dim(cluster_plan.pc_slot, start, size)
        return (v_a.at[:, sl].add(rows_of(start, size), mode="drop"),)

    (V,) = chunked_edge_reduce(
        n_edges, (jnp.zeros((cd * pd, n_pc), dtype),), vbody)
    if axis_name is not None:
        with jax.named_scope("megba.collective"):
            V = jax.lax.psum(V, axis_name)

    # T = V · Hll⁻¹ per incidence (the point block is shared by every
    # incidence of its point; Hll⁻¹ is symmetric, so T's columns double
    # as the Hll⁻¹ Vᵀ blocks the ec contraction needs).
    hinv = gather_fm(Hll_inv, cluster_plan.pc_pt)  # [pd*pd, n_pc]
    T = jnp.stack([
        sum(V[a * pd + p] * hinv[p * pd + q] for p in range(pd))
        for a in range(cd) for q in range(pd)
    ])  # [cd*pd, n_pc]

    # ec-pair contraction: corrG[(a,b), (n,J)] += Σ_q W_e[a,q] T_s[b,q]
    # over the host-enumerated (edge, same-point-slot) pairs — the
    # coupling half of G = S_d Rᵀ, chunked so the [cd*cd, chunk] block
    # rows stay VMEM-sized.  Inert padding pairs scatter to the
    # out-of-range segment Nc*C and are dropped.
    NcC = num_cameras * C

    def gbody(start, size, accs):
        (g_a,) = accs
        le = jax.lax.dynamic_slice_in_dim(cluster_plan.ec_edge, start, size)
        ls = jax.lax.dynamic_slice_in_dim(cluster_plan.ec_slot, start, size)
        sg = jax.lax.dynamic_slice_in_dim(cluster_plan.ec_seg, start, size)
        w = rows_at(le)  # [cd*pd, size]
        t = jnp.take(T, ls, axis=1, mode="clip")  # [cd*pd, size]
        block = jnp.stack([
            sum(w[a * pd + q] * t[b * pd + q] for q in range(pd))
            for a in range(cd) for b in range(cd)
        ])  # [cd*cd, size]
        return (g_a.at[:, sg].add(block, mode="drop"),)

    (corrg_rows,) = chunked_edge_reduce(
        cluster_plan.ec_edge.shape[0],
        (jnp.zeros((cd * cd, NcC), dtype),), gbody, target=_PAIR_CHUNK)
    if axis_name is not None:
        with jax.named_scope("megba.collective"):
            corrg_rows = jax.lax.psum(corrg_rows, axis_name)
    corrg = corrg_rows.reshape(cd, cd, num_cameras, C).transpose(0, 2, 3, 1)

    # Fine half Hpp_d Rᵀ: Hpp is block diagonal, so camera n contributes
    # its own block to coarse column cluster(n) only.  Fixed cameras are
    # excluded from R (their identity blocks would pollute the cluster
    # sums, and the coarse correction must never move a pinned camera);
    # their W rows are already zero, so G's rows/cols there vanish and
    # the cycle degrades to pure block-Jacobi for them.
    sel = (cluster_plan.cluster[None, :]
           == jnp.arange(C, dtype=jnp.int32)[:, None]).astype(dtype)
    if cam_fixed is not None:
        sel = sel * (1.0 - cam_fixed.astype(dtype))[None, :]
    fine = jnp.einsum("nab,Jn->anJb", Hpp_d, sel, precision=HI)
    G = fine - corrg  # [cd, Nc, C, cd] = S_d Rᵀ

    Y = None
    if smooth_omega:
        if cam_idx is None or pt_idx is None:
            raise ValueError(
                "smooth_omega > 0 needs this call's cam_idx/pt_idx edge "
                "streams so S_d can be applied to the smoothing "
                "correction (make_schur_preconditioner passes them)")
        if Minv is None:
            Minv = block_inv(Hpp_d)
        om = jnp.asarray(smooth_omega, dtype)
        # Y = D⁻¹ G₀: the damped-Jacobi smoothing correction.  Its S_d
        # image Z gives the EXACT smoothed coupling and Galerkin:
        #   G = S_d Π = G₀ − ω Z,   A_c = Πᵀ G = R G − ω Yᵀ G.
        Y = jnp.einsum("nac,cnJb->anJb", Minv, G, precision=HI)
        Z = _smooth_correction(Hpp_d, Hll_inv, rows_of, cam_idx, pt_idx,
                               Y, axis_name)
        G = G - om * Z
        A = (jnp.einsum("In,anJb->IaJb", sel, G, precision=HI)
             - om * jnp.einsum("anIc,anJb->IcJb", Y, G, precision=HI)
             ).reshape(C * cd, C * cd)
    else:
        # A_c = R G (Galerkin): tiny replicated contraction.
        A = jnp.einsum("In,anJb->IaJb", sel, G,
                       precision=HI).reshape(C * cd, C * cd)
    A = 0.5 * (A + A.T)  # symmetrise away accumulation-order roundoff
    if not factor:
        # MULTILEVEL consumes A_c as a mid-hierarchy operator — only
        # the coarsest level is factored; `ok` reports assembly health.
        zq = jnp.zeros_like(A)
        return TwoLevelCoarse(
            coarse_matrix=A, eig_q=zq,
            eig_inv=jnp.zeros(A.shape[0], A.dtype),
            ok=jnp.all(jnp.isfinite(A)), restrict_sel=sel,
            cluster=cluster_plan.cluster, G=G,
            omega=smooth_omega, Y=Y)
    # Filtered pseudo-inverse instead of a Cholesky: all-fixed /
    # edge-less clusters (exactly-zero rows) and gauge-like near-null
    # modes both land UNDER the eigenvalue floor and simply receive no
    # coarse correction, rather than NaN-ing the factor or amplifying
    # noise (_COARSE_EIG_FLOOR).
    (Q, inv), ok = dense_filtered_factor(A, _COARSE_EIG_FLOOR)
    return TwoLevelCoarse(coarse_matrix=A, eig_q=Q, eig_inv=inv, ok=ok,
                          restrict_sel=sel, cluster=cluster_plan.cluster,
                          G=G, omega=smooth_omega, Y=Y)


def _restrict(coarse: TwoLevelCoarse, r: jax.Array) -> jax.Array:
    """Πᵀ r: [cd, Nc] fine rows -> [C, cd] coarse residual (Π = Rᵀ
    plain, Rᵀ − ω Y smoothed)."""
    rc = jnp.einsum("In,an->Ia", coarse.restrict_sel, r,
                    precision=HI)  # R r  [C, cd]
    if coarse.Y is not None:
        rc = rc - coarse.omega * jnp.einsum(
            "anJb,an->Jb", coarse.Y, r, precision=HI)
    return rc


def _inject(coarse: TwoLevelCoarse, y: jax.Array) -> jax.Array:
    """Π y: [C, cd] coarse value -> [cd, Nc] fine rows.

    The plain-aggregation part gathers each camera's cluster value and
    re-applies the fixed-camera mask (selᵀ y == gather + mask, without
    materialising selᵀ); the smoothed prolongator subtracts ω Y y."""
    z = jnp.swapaxes(jnp.take(y, coarse.cluster, axis=0), 0, 1)
    z = z * jnp.max(coarse.restrict_sel, axis=0)[None, :]
    if coarse.Y is not None:
        z = z - coarse.omega * jnp.einsum(
            "anJb,Jb->an", coarse.Y, y, precision=HI)
    return z


def _level1_cycle(
    coarse: TwoLevelCoarse,
    coarse_solve: Callable[[jax.Array], jax.Array],
    ok: jax.Array,
    base_apply: Callable[[jax.Array], jax.Array],
    r: jax.Array,
) -> jax.Array:
    """One symmetrized multiplicative cycle at the fine level.

        M⁻¹ r = Π B Πᵀ r + Pᵀ D⁻¹ P r,   P = I − G B Πᵀ

    with G = S_d Π materialised at build time and B = `coarse_solve`
    any SYMMETRIC coarse approximate inverse — the exact filtered A_c⁺
    for the two-level scheme, the recursive level-2 cycle for the
    multilevel hierarchy.  Both "S applies" are [cd·Nc, C·cd]
    replicated contractions: no edge-scale ops, ZERO collectives.
    Degrades bitwise to the plain base apply when `ok` is False (the
    fallback ladder's coarse level); fixed cameras receive exactly the
    base apply by the masked selector.
    """
    rc = _restrict(coarse, r)
    y = coarse_solve(rc)
    z_c = _inject(coarse, y)
    gy = jnp.einsum("anJb,Jb->an", coarse.G, y, precision=HI)  # G y
    # Pre-smoothing residual P r = r − G B Πᵀ r; gated so the ok=False
    # ladder level is EXACTLY base_apply(r), not a perturbed smooth of
    # garbage.
    u = jnp.where(ok, r - gy, r)
    w = base_apply(u)
    # Post-correction: Π B (Gᵀ w)   (Gᵀ w = Πᵀ S_d w).
    v = jnp.einsum("anJb,an->Jb", coarse.G, w, precision=HI)
    z2 = _inject(coarse, coarse_solve(v))
    return jnp.where(ok, z_c + w - z2, w)


def two_level_cycle(
    coarse: TwoLevelCoarse,
    base_apply: Callable[[jax.Array], jax.Array],
    r: jax.Array,
) -> jax.Array:
    """One symmetrized multiplicative two-level cycle ([cd, Nc] rows):
    the `_level1_cycle` with B = the exact spectrally-filtered A_c⁺."""
    C = coarse.restrict_sel.shape[0]
    cd = r.shape[0]

    def solve(rc):
        return dense_filtered_solve(
            (coarse.eig_q, coarse.eig_inv),
            rc.reshape(C * cd)).reshape(C, cd)

    return _level1_cycle(coarse, solve, coarse.ok, base_apply, r)


# --------------------------------------------------------------------------
# Recursive camera-graph hierarchy (MULTILEVEL)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class CoarseLevel:
    """One coarse level of the multilevel hierarchy (levels >= 2).

    Mid-hierarchy levels carry the dense level operator `A`
    ([C_l·cd, C_l·cd]), its spectrally-damped block-Jacobi smoother
    (`D_inv` [C_l, cd, cd] + the damping weight `omega_s` — a traced
    scalar, 1/lambda_max(D⁻¹A) from a fixed-length power iteration at
    build, so the smoothing iteration is contraction-safe on any
    spectrum) and the aggregation `assign` ([C_l]); the COARSEST level
    carries the filtered eigen-factor instead (`eig_q`/`eig_inv`,
    assign None).  `ok` is the level's health flag (operator finite;
    at the coarsest, factor ok too)."""

    A: jax.Array
    ok: jax.Array
    D_inv: Optional[jax.Array] = None
    omega_s: Optional[jax.Array] = None
    assign: Optional[jax.Array] = None
    num_next: int = 0
    eig_q: Optional[jax.Array] = None
    eig_inv: Optional[jax.Array] = None


@dataclasses.dataclass
class MultiLevelCoarse:
    """Assembled state of one L-level preconditioner: the level-1
    Galerkin assembly (edge-scale build, `TwoLevelCoarse` without its
    factor) + the dense coarse chain.  `level_ok[l-1]` gates coarse
    level l's correction — the per-level fallback bit-field rides
    these flags into the trace code."""

    level1: TwoLevelCoarse
    chain: Tuple[CoarseLevel, ...]
    level_ok: Tuple[jax.Array, ...]


def _block_diag_inv(A: jax.Array, C: int, cd: int) -> jax.Array:
    """[C, cd, cd] inverse of the cd-block diagonal of a dense level
    operator; dead blocks (all-fixed / edge-less aggregates — exactly
    zero rows) fall back to identity so the smoother stays finite (the
    residual there is zero anyway, so they contribute nothing)."""
    idx = jnp.arange(C, dtype=jnp.int32)
    blocks = A.reshape(C, cd, C, cd)[idx, :, idx, :]
    inv = block_inv(blocks)
    eye = jnp.broadcast_to(jnp.eye(cd, dtype=A.dtype), inv.shape)
    bad = ~jnp.all(jnp.isfinite(inv), axis=(-2, -1), keepdims=True)
    return jnp.where(bad, eye, inv)


def _smoother_weight(A4: jax.Array, D_inv: jax.Array) -> jax.Array:
    """omega_s = 1 / lambda_max(D⁻¹A) by a fixed 12-step power
    iteration (dense, tiny, once per build): the damped block-Jacobi
    smoothing iteration x += omega_s D⁻¹ r then has spectral radius
    ~<= 1 < 2 on ANY level spectrum, which is exactly the SPD condition
    of the symmetric V(1,1) cycle it smooths inside."""
    C, cd = D_inv.shape[0], D_inv.shape[1]
    v = jnp.ones((C, cd), A4.dtype)
    nrm = jnp.asarray(1.0, A4.dtype)
    for _ in range(12):
        w = jnp.einsum("iab,ib->ia", D_inv,
                       jnp.einsum("iajb,jb->ia", A4, v, precision=HI),
                       precision=HI)
        nrm = jnp.sqrt(jnp.sum(w * w))
        v = w / jnp.maximum(nrm, jnp.asarray(1e-30, A4.dtype))
    om = 1.0 / jnp.maximum(nrm, jnp.asarray(1.0, A4.dtype))
    return jnp.where(jnp.isfinite(om), om, jnp.asarray(1.0, A4.dtype))


@jax.named_scope("megba.precond_coarse_build")
def build_multilevel_coarse(
    Hpp_d: jax.Array,
    Hll_inv: jax.Array,
    W: Optional[jax.Array],
    Jc: Optional[jax.Array],
    Jp: Optional[jax.Array],
    multilevel_plan,
    compute_kind: ComputeKind,
    axis_name: Optional[str] = None,
    cam_fixed: Optional[jax.Array] = None,
    plans=None,
    smooth_omega: float = 0.0,
    Minv: Optional[jax.Array] = None,
    cam_idx: Optional[jax.Array] = None,
    pt_idx: Optional[jax.Array] = None,
) -> MultiLevelCoarse:
    """Assemble the full hierarchy from one host-planned
    ops/segtiles.DeviceMultiLevelPlan.

    Level 1 is the (optionally smoothed) edge-scale Galerkin build
    (`build_two_level_coarse`, unfactored when deeper levels exist);
    every further level l+1 is the PLAIN-aggregation dense Galerkin
        A_{l+1} = R_l A_l R_lᵀ
    over the host-planned assignment — a tiny replicated contraction
    (the cluster counts shrink geometrically), so the hierarchy adds
    ZERO collectives beyond level 1's build psums and nothing at all
    inside the PCG while body.  Only the coarsest level pays the dense
    filtered pseudo-inverse (`dense_filtered_factor`); mid-hierarchy
    levels smooth with their own block-Jacobi diagonal."""
    cplan = multilevel_plan.base
    depth_assign = len(multilevel_plan.assign)
    level1 = build_two_level_coarse(
        Hpp_d, Hll_inv, W, Jc, Jp, cplan, compute_kind,
        axis_name=axis_name, cam_fixed=cam_fixed, plans=plans,
        smooth_omega=smooth_omega, Minv=Minv, cam_idx=cam_idx,
        pt_idx=pt_idx, factor=(depth_assign == 0))
    cd = Hpp_d.shape[-1]
    dtype = Hpp_d.dtype
    A = level1.coarse_matrix
    if depth_assign == 0:
        # Two levels deep: level 1 IS the coarsest — its factor was
        # built above (factor=True), don't factor twice.
        chain = [CoarseLevel(A=A, ok=level1.ok, eig_q=level1.eig_q,
                             eig_inv=level1.eig_inv)]
    else:
        chain = []
        sizes = multilevel_plan.level_sizes
        for i, assign in enumerate(multilevel_plan.assign):
            Cl, Cn = int(sizes[i]), int(sizes[i + 1])
            sel = (assign[None, :] == jnp.arange(
                Cn, dtype=jnp.int32)[:, None]).astype(dtype)
            A4 = A.reshape(Cl, cd, Cl, cd)
            D_inv = _block_diag_inv(A, Cl, cd)
            chain.append(CoarseLevel(
                A=A, ok=jnp.all(jnp.isfinite(A)), D_inv=D_inv,
                omega_s=_smoother_weight(A4, D_inv),
                assign=assign, num_next=Cn))
            G4 = jnp.einsum("iakb,Jk->iaJb", A4, sel,
                            precision=HI)  # A R_lᵀ
            A_next = jnp.einsum("Ii,iaJb->IaJb", sel, G4,
                                precision=HI).reshape(Cn * cd, Cn * cd)
            A = 0.5 * (A_next + A_next.T)
        # Coarsest level: the only dense factor in the hierarchy.
        (Q, inv), okc = dense_filtered_factor(A, _COARSE_EIG_FLOOR)
        chain.append(CoarseLevel(A=A, ok=okc, eig_q=Q, eig_inv=inv))
    # level_ok[l-1] gates coarse level l's correction: a level is
    # usable when its OWN operator assembled finite (the coarsest
    # additionally needs its factor) AND every ancestor is — a bad
    # level makes all deeper levels unreachable, so the bit-field
    # reads as "the cycle truncated here".
    gated = []
    alive = jnp.bool_(True)
    for lvl in chain:
        alive = alive & lvl.ok
        gated.append(alive)
    return MultiLevelCoarse(level1=level1, chain=tuple(chain),
                            level_ok=tuple(gated))


def _chain_solve(chain: Tuple[CoarseLevel, ...], level_ok, i: int,
                 rc: jax.Array) -> jax.Array:
    """Approximate A_{i+1}⁻¹ rc ([C, cd]) by a recursive SYMMETRIC
    V(1,1) cycle over the dense chain: damped block-Jacobi pre-smooth,
    recursive coarse correction on the true residual, damped post-
    smooth.  Static recursion depth (the hierarchy is host-planned),
    all replicated dense work, SPD whenever the smoothing iteration
    contracts — which `omega_s` = 1/lambda_max(D⁻¹A) guarantees.  The
    residual-based form matters: unlike the fine level (where the
    materialised G avoids edge-scale S applies and the coarse solve is
    exact-or-recursive), a MID-hierarchy correction is inexact, and
    re-smoothing its residual is what keeps the cycle's quality close
    to the exact two-level solve instead of degrading with depth."""
    lvl = chain[i]
    C, cd = rc.shape
    if lvl.assign is None:  # coarsest: exact filtered solve
        return dense_filtered_solve(
            (lvl.eig_q, lvl.eig_inv), rc.reshape(C * cd)).reshape(C, cd)
    ok_next = level_ok[i + 1]
    A4 = lvl.A.reshape(C, cd, C, cd)

    def smooth(x):
        return lvl.omega_s * jnp.einsum("iab,ib->ia", lvl.D_inv, x,
                                        precision=HI)

    def amat(x):
        return jnp.einsum("iajb,jb->ia", A4, x, precision=HI)

    z1 = smooth(rc)
    r1 = rc - amat(z1)
    rn = jnp.zeros((lvl.num_next, cd), rc.dtype).at[lvl.assign].add(r1)
    zc = jnp.take(_chain_solve(chain, level_ok, i + 1, rn), lvl.assign,
                  axis=0)  # R_lᵀ B (R_l r1)
    z2 = z1 + jnp.where(ok_next, zc, jnp.zeros_like(zc))
    r2 = rc - amat(z2)
    return z2 + smooth(r2)


def multilevel_cycle(
    mlc: MultiLevelCoarse,
    base_apply: Callable[[jax.Array], jax.Array],
    r: jax.Array,
) -> jax.Array:
    """One recursive L-level V-cycle ([cd, Nc] rows): the fine-level
    symmetrized multiplicative cycle with B = the level-2 recursive
    cycle (or the exact coarse solve when the hierarchy is 2 deep).
    SPD by induction: every level composes Π B Πᵀ + Pᵀ D⁻¹ P from an
    SPD B and a PD smoother, exactly like the two-level proof."""
    cd = r.shape[0]

    def solve(rc):
        return _chain_solve(mlc.chain, mlc.level_ok, 0,
                            rc.reshape(-1, cd))

    return _level1_cycle(mlc.level1, solve, mlc.level_ok[0], base_apply, r)


# --------------------------------------------------------------------------
# Factory
# --------------------------------------------------------------------------


def make_schur_preconditioner(
    kind: PrecondKind,
    block_kind: PreconditionerKind,
    Hpp_d: jax.Array,
    Hll_inv: jax.Array,
    W: Optional[jax.Array],
    Jc: Optional[jax.Array],
    Jp: Optional[jax.Array],
    cam_idx: jax.Array,
    pt_idx: jax.Array,
    num_cameras: int,
    compute_kind: ComputeKind,
    axis_name: Optional[str],
    cam_sorted: bool,
    neumann_order: int = 2,
    plans=None,
    cluster_plan=None,
    cam_fixed=None,
    s_matvec: Optional[Callable[[jax.Array], jax.Array]] = None,
    smooth_omega: float = 0.0,
    bf16: bool = False,
    fused_kernels: bool = False,
) -> Tuple[Callable[[jax.Array], jax.Array], jax.Array]:
    """Build the reduced-system preconditioner apply for one solve.

    Returns `(apply, fallback_code)`: `apply(r [cd, Nc]) -> [cd, Nc]`
    runs inside the PCG while body; `fallback_code` is the enum-coded
    per-level fallback count (encode_precond_fallback) for the trace.
    `kind` picks the operator family (PrecondKind), `block_kind` the
    base block diagonal every family smooths with (PreconditionerKind).
    All operands are the damped, already-materialised solve quantities;
    `s_matvec` (the CG's own S·p closure) is required by NEUMANN only.
    `cluster_plan` is a DeviceClusterPlan for TWO_LEVEL, a
    DeviceMultiLevelPlan for MULTILEVEL; `smooth_omega` > 0 turns on
    the smoothed-aggregation prolongator for both coarse-space kinds.

    `bf16` (SolverOption.bf16) stores the inverted block diagonal as a
    bfloat16 copy and applies it through `cam_block_matvec_bf16` (bf16
    operands, f32 accumulation via preferred_element_type) — the base
    apply is the per-iteration bandwidth-heavy operand of every family
    (Nc·cd² block bytes per CG step), and the equilibrated M⁻¹ is
    unit-scale, well inside bf16's range.  The block diagonal itself
    (and the SCHUR_DIAG correction, the coarse Galerkin builds, and
    every coarse solve) is still COMPUTED in f32; only the apply's
    stored operand narrows — the allowed-surface contract the HLO
    auditor pins.

    `fused_kernels` (SolverOption.fused_kernels) replaces the base
    apply's einsum with the fused block-diagonal Pallas kernel
    (ops/fused.fused_block_diag_apply): M⁻¹ is laid out ONCE as
    feature-major [cd², Nc] rows and the apply runs as one kernel pass
    over camera blocks — same bf16-operand / f32-accumulation contract
    as `cam_block_matvec_bf16` when `bf16` is also set.  Every family
    smooths with the fused base apply; coarse builds/solves are
    untouched.
    """
    if block_kind == PreconditionerKind.SCHUR_DIAG:
        Minv, n_bad = _schur_diag_precond(
            Hpp_d, Hll_inv, W, Jc, Jp, cam_idx, pt_idx, num_cameras,
            compute_kind, axis_name, cam_sorted, plans=plans)
    else:
        Minv = block_inv(Hpp_d)  # reference block-Jacobi (Hpp)
        n_bad = jnp.int32(0)

    if fused_kernels:
        from megba_tpu.ops import fused as _fused

        Hrows = _fused.block_diag_rows(
            Minv.astype(jnp.bfloat16) if bf16 else Minv)
        _interp = not _fused.kernels_supported()

        def base_apply(r):
            return _fused.fused_block_diag_apply(
                Hrows, r, bf16_operands=bf16, interpret=_interp,
                name="fused_block_diag_cam")
    elif bf16:
        Minv_bf16 = Minv.astype(jnp.bfloat16)

        def base_apply(r):
            return cam_block_matvec_bf16(Minv_bf16, r)
    else:
        def base_apply(r):
            return cam_block_matvec(Minv, r)

    if kind == PrecondKind.JACOBI:
        return base_apply, encode_precond_fallback(n_bad)

    if kind == PrecondKind.NEUMANN:
        if s_matvec is None:
            raise ValueError("NEUMANN preconditioner needs the S matvec")
        order = int(neumann_order)

        @jax.named_scope("megba.precond_neumann")
        def neumann_apply(r):
            # Horner form of Σ_{i<=k} E^i D⁻¹ r, E = I − D⁻¹S: each
            # step is one S apply (the 2-psum product) + one block
            # solve.  k is static — the unrolled chain lives inside the
            # fused while body.
            z = base_apply(r)
            for _ in range(order):
                z = z + base_apply(r - s_matvec(z))
            return z

        return neumann_apply, encode_precond_fallback(n_bad)

    if kind not in (PrecondKind.TWO_LEVEL,
                    PrecondKind.MULTILEVEL):  # pragma: no cover - closed
        raise ValueError(f"unknown precond kind {kind}")
    if cluster_plan is None:
        raise ValueError(
            f"precond={kind.name} needs a camera-cluster plan operand; "
            "the flat_solve lowering builds one automatically "
            "(ops/segtiles.cached_cluster_plan / cached_multilevel_plan)"
            " — direct schur_pcg_solve callers must pass cluster_plan=")

    if kind == PrecondKind.TWO_LEVEL:
        coarse = build_two_level_coarse(
            Hpp_d, Hll_inv, W, Jc, Jp, cluster_plan, compute_kind,
            axis_name=axis_name, cam_fixed=cam_fixed, plans=plans,
            smooth_omega=smooth_omega, Minv=Minv, cam_idx=cam_idx,
            pt_idx=pt_idx)

        @jax.named_scope("megba.precond_two_level")
        def two_level_apply(r):
            return two_level_cycle(coarse, base_apply, r)

        fallback = encode_precond_fallback(
            n_bad, jnp.where(coarse.ok, jnp.int32(0), jnp.int32(1)))
        return two_level_apply, fallback

    mlc = build_multilevel_coarse(
        Hpp_d, Hll_inv, W, Jc, Jp, cluster_plan, compute_kind,
        axis_name=axis_name, cam_fixed=cam_fixed, plans=plans,
        smooth_omega=smooth_omega, Minv=Minv, cam_idx=cam_idx,
        pt_idx=pt_idx)

    @jax.named_scope("megba.precond_multilevel")
    def multilevel_apply(r):
        return multilevel_cycle(mlc, base_apply, r)

    # Per-level bit-field: bit l-1 set when coarse level l's correction
    # is out of the cycle (its operator — or an ancestor's — degraded).
    bits = jnp.int32(0)
    for i, ok_l in enumerate(mlc.level_ok):
        bits = bits + jnp.where(ok_l, jnp.int32(0), jnp.int32(1 << i))
    return multilevel_apply, encode_precond_fallback(n_bad, bits)


__all__ = [
    "FALLBACK_BLOCK_RADIX",
    "FALLBACK_MAX_COARSE_LEVELS",
    "CoarseLevel",
    "MultiLevelCoarse",
    "TwoLevelCoarse",
    "block_inv",
    "build_multilevel_coarse",
    "build_two_level_coarse",
    "cam_block_matvec",
    "cam_block_matvec_bf16",
    "decode_precond_fallback",
    "decode_precond_fallback_levels",
    "encode_precond_fallback",
    "make_schur_preconditioner",
    "multilevel_cycle",
    "two_level_cycle",
    "_schur_diag_precond",
]
