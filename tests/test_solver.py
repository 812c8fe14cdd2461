"""Linear system + Schur-PCG tests vs dense direct solve (SURVEY.md §4c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megba_tpu.common import ComputeKind, JacobianMode
from megba_tpu.io.synthetic import make_synthetic_bal
from megba_tpu.linear_system import build_schur_system, damp_blocks, weight_system_inputs
from megba_tpu.ops.residuals import make_residual_jacobian_fn
from megba_tpu.solver import dense_reference_solve, schur_pcg_solve


def build_test_system(seed=0, num_cameras=3, num_points=12, compute_kind=ComputeKind.IMPLICIT,
                      cam_fixed=None, pt_fixed=None):
    s = make_synthetic_bal(num_cameras=num_cameras, num_points=num_points, seed=seed)
    cams = jnp.asarray(s.cameras0.T)
    pts = jnp.asarray(s.points0.T)
    cam_idx = jnp.asarray(s.cam_idx)
    pt_idx = jnp.asarray(s.pt_idx)
    obs = jnp.asarray(s.obs.T)
    mask = jnp.ones(obs.shape[1])
    f = make_residual_jacobian_fn(mode=JacobianMode.ANALYTICAL)
    r, Jc, Jp = f(cams[:, cam_idx], pts[:, pt_idx], obs)
    r, Jc, Jp = weight_system_inputs(r, Jc, Jp, cam_idx, pt_idx, mask,
                                     cam_fixed=cam_fixed, pt_fixed=pt_fixed)
    system = build_schur_system(
        r, Jc, Jp, cam_idx, pt_idx, num_cameras, num_points,
        compute_kind=compute_kind, cam_fixed=cam_fixed, pt_fixed=pt_fixed)
    return system, r, Jc, Jp, cam_idx, pt_idx


@pytest.mark.parametrize("d", [1, 2, 3, 9])
def test_block_inv_matches_numpy(d):
    from megba_tpu.solver import block_inv
    r = np.random.default_rng(0)
    A = r.normal(size=(7, d, d))
    spd = A @ A.transpose(0, 2, 1) + 3.0 * np.eye(d)  # damped-SPD-like
    got = block_inv(jnp.asarray(spd))
    np.testing.assert_allclose(got, np.linalg.inv(spd), rtol=1e-9, atol=1e-11)


def test_hessian_blocks_match_dense_assembly():
    system, r, Jc, Jp, cam_idx, pt_idx = build_test_system()
    # Assemble J^T J brute-force per camera from the edge list (rows ->
    # per-edge [od, cd] blocks via reshape of the feature axis).
    nE = r.shape[1]
    for c in range(3):
        H = np.zeros((9, 9))
        g = np.zeros(9)
        for e in range(nE):
            if int(cam_idx[e]) == c:
                Je = np.asarray(Jc[:, e]).reshape(2, 9)
                H += Je.T @ Je
                g -= Je.T @ np.asarray(r[:, e])
        np.testing.assert_allclose(system.Hpp[c], H, rtol=1e-10, atol=1e-12)
        np.testing.assert_allclose(system.g_cam[:, c], g, rtol=1e-10, atol=1e-12)


def test_damping():
    system, *_ = build_test_system()
    region = jnp.asarray(10.0)
    damped = damp_blocks(system.Hpp, region)
    expect = np.asarray(system.Hpp).copy()
    for i in range(expect.shape[0]):
        np.fill_diagonal(expect[i], np.diag(expect[i]) * 1.1)
    np.testing.assert_allclose(damped, expect, rtol=1e-12)


@pytest.mark.parametrize("compute_kind", [ComputeKind.IMPLICIT, ComputeKind.EXPLICIT])
def test_pcg_matches_dense(compute_kind):
    system, r, Jc, Jp, cam_idx, pt_idx = build_test_system(compute_kind=compute_kind)
    region = jnp.asarray(100.0)
    dx_cam_d, dx_pt_d = dense_reference_solve(system, Jc, Jp, cam_idx, pt_idx, region)
    out = schur_pcg_solve(
        system, Jc, Jp, cam_idx, pt_idx, region,
        max_iter=500, tol=1e-14, refuse_ratio=1e30, compute_kind=compute_kind)
    np.testing.assert_allclose(out.dx_cam, dx_cam_d, rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(out.dx_pt, dx_pt_d, rtol=1e-6, atol=1e-8)


def test_pcg_jit_compiles():
    system, r, Jc, Jp, cam_idx, pt_idx = build_test_system()
    f = jax.jit(
        lambda sys_, Jc_, Jp_, region: schur_pcg_solve(
            sys_, Jc_, Jp_, cam_idx, pt_idx, region, max_iter=50, tol=1e-10,
        )
    )
    out = f(system, Jc, Jp, jnp.asarray(50.0))
    assert np.all(np.isfinite(out.dx_cam)) and np.all(np.isfinite(out.dx_pt))
    assert int(out.iterations) > 0


@pytest.mark.parametrize("compute_kind", [ComputeKind.IMPLICIT, ComputeKind.EXPLICIT])
def test_pcg_mixed_precision_close_to_full(compute_kind):
    # bf16 coupling products with f32 accumulation (BASELINE.md config 5)
    # must land near the full-precision solution.
    system, r, Jc, Jp, cam_idx, pt_idx = build_test_system(compute_kind=compute_kind)
    region = jnp.asarray(100.0)
    full = schur_pcg_solve(system, Jc, Jp, cam_idx, pt_idx, region,
                           max_iter=200, tol=1e-12, refuse_ratio=1e30,
                           compute_kind=compute_kind)
    mixed = schur_pcg_solve(system, Jc, Jp, cam_idx, pt_idx, region,
                            max_iter=200, tol=1e-12, refuse_ratio=1e30,
                            compute_kind=compute_kind, mixed_precision=True)
    assert mixed.dx_cam.dtype == full.dx_cam.dtype  # Krylov state stays full precision
    # bf16 coupling products give an inexact Newton step (LM's accept /
    # reject absorbs this); require direction agreement, not equality.
    scale = float(jnp.max(jnp.abs(full.dx_cam)))
    np.testing.assert_allclose(mixed.dx_cam, full.dx_cam, atol=0.25 * scale)
    cos = float(jnp.sum(mixed.dx_cam * full.dx_cam)) / (
        float(jnp.linalg.norm(mixed.dx_cam)) * float(jnp.linalg.norm(full.dx_cam)))
    assert cos > 0.95


@pytest.mark.parametrize("compute_kind", [ComputeKind.IMPLICIT, ComputeKind.EXPLICIT])
def test_schur_diag_preconditioner(compute_kind):
    # A preconditioner must not change WHAT PCG converges to, only how it
    # gets there: SCHUR_DIAG's solution matches the dense direct solve.
    # (Iteration counts are problem-dependent — see PreconditionerKind.)
    from megba_tpu.common import PreconditionerKind
    system, r, Jc, Jp, cam_idx, pt_idx = build_test_system(
        seed=3, compute_kind=compute_kind)
    region = jnp.asarray(100.0)
    kw = dict(max_iter=500, tol=1e-13, tol_relative=True, refuse_ratio=1e30,
              compute_kind=compute_kind)
    sd = schur_pcg_solve(system, Jc, Jp, cam_idx, pt_idx, region,
                         preconditioner=PreconditionerKind.SCHUR_DIAG, **kw)
    dx_cam_d, dx_pt_d = dense_reference_solve(system, Jc, Jp, cam_idx, pt_idx, region)
    np.testing.assert_allclose(sd.dx_cam, dx_cam_d, rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(sd.dx_pt, dx_pt_d, rtol=1e-5, atol=1e-8)
    assert int(sd.iterations) > 0


def test_relative_tolerance_mode():
    # tol_relative reinterprets tol as a fraction of rho0: a modest 1e-8
    # relative tolerance must reach (near) the dense answer regardless of
    # the problem's cost scale, where the same 1e-8 ABSOLUTE tol would
    # run to max_iter on a large-scale problem or quit instantly on a
    # tiny one.
    system, r, Jc, Jp, cam_idx, pt_idx = build_test_system(seed=5)
    region = jnp.asarray(100.0)
    dx_cam_d, dx_pt_d = dense_reference_solve(system, Jc, Jp, cam_idx, pt_idx, region)
    out = schur_pcg_solve(system, Jc, Jp, cam_idx, pt_idx, region,
                          max_iter=500, tol=1e-12, tol_relative=True,
                          refuse_ratio=1e30)
    np.testing.assert_allclose(out.dx_cam, dx_cam_d, rtol=1e-4, atol=1e-7)
    # With an absurd absolute tol the loop would exit immediately; the
    # relative mode must actually iterate.
    assert int(out.iterations) > 0


def test_refuse_ratio_guard():
    # With the reference's default refuse_ratio=1.0, the solver must stop
    # as soon as rho is non-decreasing and restore the best iterate
    # (schur_pcg_solver.cu:288-296 semantics) — fewer iterations than the
    # effectively-disabled guard, and still a usable descent direction.
    system, r, Jc, Jp, cam_idx, pt_idx = build_test_system(seed=4)
    region = jnp.asarray(1e3)
    guarded = schur_pcg_solve(system, Jc, Jp, cam_idx, pt_idx, region,
                              max_iter=300, tol=1e-30, refuse_ratio=1.0)
    free = schur_pcg_solve(system, Jc, Jp, cam_idx, pt_idx, region,
                           max_iter=300, tol=1e-30, refuse_ratio=1e30)
    # Strictly fewer: in this seeded scenario the guard fires at ~9 vs 40
    # unguarded iterations, so equality would mean the guard is broken.
    assert int(guarded.iterations) < int(free.iterations)
    assert np.all(np.isfinite(guarded.dx_cam))
    # The guarded solution still reduces the quadratic model vs dx=0:
    # g^T dx > 0 for a descent direction of 1/2 x^T H x - g^T x.
    descent = float(jnp.sum(system.g_cam * guarded.dx_cam)
                    + jnp.sum(system.g_pt * guarded.dx_pt))
    assert descent > 0


def test_fixed_camera_gets_zero_update():
    cam_fixed = jnp.asarray([True, False, False])
    system, r, Jc, Jp, cam_idx, pt_idx = build_test_system(cam_fixed=cam_fixed)
    out = schur_pcg_solve(system, Jc, Jp, cam_idx, pt_idx, jnp.asarray(100.0),
                          max_iter=300, tol=1e-13, refuse_ratio=1e30)
    np.testing.assert_allclose(out.dx_cam[:, 0], np.zeros(9), atol=1e-12)
    assert float(jnp.max(jnp.abs(out.dx_cam[:, 1:]))) > 0


def test_edgeless_vertex_is_inert_not_nan():
    # A point with no observations (possible in filtered real datasets)
    # must get a zero update, not NaN-poison the solve.
    s = make_synthetic_bal(num_cameras=3, num_points=12, seed=2)
    cams, pts0 = jnp.asarray(s.cameras0.T), np.asarray(s.points0)
    pts = jnp.asarray(np.concatenate([pts0, [[9.0, 9.0, 9.0]]]).T)  # orphan point 12
    cam_idx, pt_idx, obs = jnp.asarray(s.cam_idx), jnp.asarray(s.pt_idx), jnp.asarray(s.obs.T)
    f = make_residual_jacobian_fn(mode=JacobianMode.ANALYTICAL)
    r, Jc, Jp = f(cams[:, cam_idx], pts[:, pt_idx], obs)
    r, Jc, Jp = weight_system_inputs(r, Jc, Jp, cam_idx, pt_idx, jnp.ones(len(s.obs)))
    system = build_schur_system(r, Jc, Jp, cam_idx, pt_idx, 3, 13)
    out = schur_pcg_solve(system, Jc, Jp, cam_idx, pt_idx, jnp.asarray(100.0),
                          max_iter=300, tol=1e-13, refuse_ratio=1e30)
    assert np.all(np.isfinite(out.dx_cam)) and np.all(np.isfinite(out.dx_pt))
    np.testing.assert_allclose(out.dx_pt[:, 12], np.zeros(3), atol=1e-14)


def test_padding_edges_are_inert():
    # Same system with 5 extra masked edges must produce identical blocks.
    s = make_synthetic_bal(num_cameras=3, num_points=12, seed=1)
    cams, pts = jnp.asarray(s.cameras0.T), jnp.asarray(s.points0.T)
    f = make_residual_jacobian_fn(mode=JacobianMode.ANALYTICAL)

    def build(cam_idx, pt_idx, obs, mask):
        r, Jc, Jp = f(cams[:, cam_idx], pts[:, pt_idx], obs)
        r, Jc, Jp = weight_system_inputs(r, Jc, Jp, cam_idx, pt_idx, mask)
        return build_schur_system(r, Jc, Jp, cam_idx, pt_idx, 3, 12)

    base = build(jnp.asarray(s.cam_idx), jnp.asarray(s.pt_idx), jnp.asarray(s.obs.T),
                 jnp.ones(len(s.obs)))
    pad = 5
    cam_idx_p = jnp.concatenate([jnp.asarray(s.cam_idx), jnp.zeros(pad, jnp.int32)])
    pt_idx_p = jnp.concatenate([jnp.asarray(s.pt_idx), jnp.zeros(pad, jnp.int32)])
    obs_p = jnp.concatenate([jnp.asarray(s.obs.T), jnp.full((2, pad), 123.0)], axis=1)
    mask_p = jnp.concatenate([jnp.ones(len(s.obs)), jnp.zeros(pad)])
    padded = build(cam_idx_p, pt_idx_p, obs_p, mask_p)
    np.testing.assert_allclose(padded.Hpp, base.Hpp, rtol=1e-12)
    np.testing.assert_allclose(padded.Hll, base.Hll, rtol=1e-12)
    np.testing.assert_allclose(padded.g_cam, base.g_cam, rtol=1e-12)
    np.testing.assert_allclose(padded.g_pt, base.g_pt, rtol=1e-12)


# ---------------------------------------------- fused CG: k products for k
# iterations, iterates unchanged


def _pcg_k_plus_1(matvec, precond, b, max_iter, tol, refuse_ratio,
                  tol_relative, x0=None, **_):
    """The fused (Chronopoulos-Gear) PCG as it ran with its matvec at the
    END of the loop body: one product before the loop primed the
    recurrence, so k iterations ran k+1 products.  The reference the
    matvec-first body must reproduce bit for bit; it returns _pcg_core's
    tuple (restarts 0, broken False: clean solves only)."""
    from megba_tpu.solver.pcg import _TINY_RHO, _dot

    tm = jax.tree_util.tree_map

    def tdot(a, c):
        return jax.tree_util.tree_reduce(lambda acc, v: acc + v,
                                         tm(_dot, a, c))

    def axpy(a, x, y):
        return tm(lambda xi, yi: yi + a * xi, x, y)

    def select(pred, a, c):
        return tm(lambda ai, ci: jnp.where(pred, ai, ci), a, c)

    if x0 is None:
        x_init, r0 = tm(jnp.zeros_like, b), b
        u0 = precond(r0)
        rho0 = tdot(r0, u0)
        rhs_energy, r0_ratio = rho0, jnp.ones_like(rho0)
    else:
        r0 = axpy(jnp.asarray(-1.0, jax.tree_util.tree_leaves(b)[0].dtype),
                  matvec(x0), b)
        u0 = precond(r0)
        rho0 = tdot(r0, u0)
        ub = precond(b)
        rhs_energy = tdot(b, ub)
        r0_ratio = jnp.abs(rho0) / jnp.maximum(
            jnp.abs(rhs_energy), jnp.asarray(_TINY_RHO, rho0.dtype))
        use_ws = jnp.abs(rho0) <= jnp.abs(rhs_energy)
        x_init = select(use_ws, x0, tm(jnp.zeros_like, b))
        r0 = select(use_ws, r0, b)
        u0 = select(use_ws, u0, ub)
        rho0 = jnp.where(use_ws, rho0, rhs_energy)
    threshold = (jnp.maximum(tol * jnp.abs(rhs_energy),
                             jnp.asarray(_TINY_RHO, rho0.dtype))
                 if tol_relative else tol)
    w0 = matvec(u0)
    delta0 = tdot(u0, w0)
    alpha0 = rho0 / jnp.where(delta0 == 0, jnp.ones_like(delta0), delta0)

    def cond(state):
        k, _, _, _, _, _, rho, _, _, refused = state
        return (k < max_iter) & (jnp.abs(rho) >= threshold) & (~refused)

    def body(state):
        k, x, r, p, s, alpha, rho, rho_min, x_best, _ = state
        x = axpy(alpha, p, x)
        r = axpy(-alpha, s, r)
        u = precond(r)
        w = matvec(u)
        rho_new = tdot(r, u)
        delta = tdot(u, w)
        beta = rho_new / rho
        alpha = rho_new / (delta - beta * rho_new / alpha)
        p = axpy(beta, p, u)
        s = axpy(beta, s, w)
        refused = jnp.abs(rho_new) > refuse_ratio * rho_min
        improved = jnp.abs(rho_new) < rho_min
        rho_min = jnp.where(improved, jnp.abs(rho_new), rho_min)
        x_best = select(improved, x, x_best)
        return (k + 1, x, r, p, s, alpha, rho_new, rho_min, x_best, refused)

    state0 = (jnp.int32(0), x_init, r0, u0, w0, alpha0, rho0, jnp.abs(rho0),
              x_init, jnp.bool_(False))
    k, x, _, _, _, _, rho, _, x_best, refused = jax.lax.while_loop(
        cond, body, state0)
    return (select(~refused, x, x_best), k, rho, r0_ratio, jnp.int32(0),
            jnp.bool_(False))


def _dense_spd(seed=0, n=12, shift=None):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n))
    shift = 0.5 * n if shift is None else shift
    mat = jnp.asarray(a @ a.T + shift * np.eye(n))
    b = jnp.asarray(rng.standard_normal(n))
    return mat, b


def _counting(matvec):
    """Wrap `matvec` so every EXECUTED product (eager, or one trip of a
    while body) bumps a host-side counter."""
    calls = [0]

    def bump():
        calls[0] += 1

    def counted(v):
        jax.debug.callback(bump)
        return matvec(v)

    return counted, calls


# Dense cases: diagonal shift, tol, refuse_ratio and whether to
# warm-start.  "refused" is ill-conditioned enough that the refuse guard
# (rho grew) stops it before tol or max_iter.
_DENSE_CASES = {
    "cold": (8.0, 1e-20, 1e30, False),
    "warm": (8.0, 1e-20, 1e30, True),
    "refused": (0.05, 1e-30, 1.0, False),
}


@pytest.mark.parametrize("guard", [False, True])
@pytest.mark.parametrize("case", sorted(_DENSE_CASES))
def test_fused_pcg_bitwise_matches_k_plus_1_recurrence_dense(case, guard):
    from megba_tpu.solver.pcg import _pcg_core

    shift, tol, refuse, warm = _DENSE_CASES[case]
    mat, b = _dense_spd(seed=0, n=16, shift=shift)
    diag = jnp.diagonal(mat)
    x0 = 0.5 * jnp.linalg.solve(mat, b) if warm else None
    args = (lambda v: mat @ v, lambda r: r / diag)
    rest = (60, tol, refuse, False)
    # Jitted, as every caller runs it (see _SCHUR_CASES).
    ref = jax.jit(lambda b, x0: _pcg_k_plus_1(*args, b, *rest, x0=x0))(
        b, x0)
    new = jax.jit(lambda b, x0: _pcg_core(
        *args, b, *rest, x0=x0, guard=guard, max_restarts=2))(b, x0)
    for got, want in zip(new[:4], ref[:4]):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(new[4]) == 0 and not bool(new[5])
    k = int(new[1])
    assert 1 < k < 60
    if case == "refused":
        assert abs(float(new[2])) >= tol  # the guard stopped it, not tol


# Schur cases on a small BA system: region, tol, refuse_ratio, warm start.
# Each solve is jitted, as every caller runs it: op-by-op (eager) the
# priming product outside the loop rounds differently from the same
# product compiled into the loop body.  The warm start's first CG step
# is the one place where the two bodies are not bit for bit alike on
# XLA:CPU: the first step's compensated dots (<r0, u0>, <u0, A u0>) are
# compiled next to the warm-start residual product in one body and
# inside the loop in the other, XLA:CPU contracts their multiply-adds
# into FMAs by fusion context, and alpha0 comes out one ulp apart.  That
# case is held to 1e-11 instead; its count and r0_ratio stay exact.
_SCHUR_CASES = {
    "cold": (100.0, 1e-14, 1e30, False),
    "warm": (100.0, 1e-6, 1e30, True),
    "refused": (1e3, 1e-30, 1.0, False),
}


@pytest.mark.parametrize("guard", [False, True])
@pytest.mark.parametrize("case", sorted(_SCHUR_CASES))
def test_fused_pcg_bitwise_matches_k_plus_1_recurrence_schur(
        case, guard, monkeypatch):
    import megba_tpu.solver.pcg as pcg_mod

    region, tol, refuse, warm = _SCHUR_CASES[case]
    system, r, Jc, Jp, cam_idx, pt_idx = build_test_system(
        seed=4 if case == "refused" else 0)
    region = jnp.asarray(region)
    x0 = None
    if warm:
        x0 = 0.5 * schur_pcg_solve(
            system, Jc, Jp, cam_idx, pt_idx, region, max_iter=300,
            tol=1e-20, refuse_ratio=1e30).dx_cam

    def solve():
        # A fresh closure per call: jit traces it anew, after the patch.
        return jax.jit(lambda system, region, x0: schur_pcg_solve(
            system, Jc, Jp, cam_idx, pt_idx, region, max_iter=300, tol=tol,
            refuse_ratio=refuse, x0=x0, guard=guard, max_restarts=2))(
                system, region, x0)

    new = solve()
    monkeypatch.setattr(pcg_mod, "_pcg_core", _pcg_k_plus_1)
    ref = solve()
    for name in ("iterations", "r0_ratio"):
        assert np.array_equal(np.asarray(getattr(new, name)),
                              np.asarray(getattr(ref, name))), name
    for name in ("dx_cam", "dx_pt", "rho"):
        got, want = np.asarray(getattr(new, name)), np.asarray(
            getattr(ref, name))
        if warm:
            np.testing.assert_allclose(got, want, rtol=0,
                                       atol=1e-11 * np.max(np.abs(want)))
        else:
            assert np.array_equal(got, want), name
    assert 1 < int(new.iterations) < 300
    if case == "refused":
        assert abs(float(new.rho)) >= tol


@pytest.mark.parametrize("guard", [False, True])
@pytest.mark.parametrize("case", ["cold", "converged_at_start", "warm"])
def test_fused_pcg_runs_one_matvec_per_iteration(case, guard):
    from megba_tpu.solver.pcg import _pcg_core

    mat, b = _dense_spd(seed=5, n=16)
    matvec, calls = _counting(lambda v: mat @ v)
    # rho0 = <b, b> is far under the absurd tol: the loop never runs.
    tol = 1e6 if case == "converged_at_start" else 1e-20
    x0 = 0.5 * jnp.linalg.solve(mat, b) if case == "warm" else None
    _, k, _, _, _, _ = _pcg_core(matvec, lambda r: r, b, 60, tol, 1e30,
                                 False, x0=x0, guard=guard, max_restarts=2)
    jax.effects_barrier()
    k = int(k)
    if case == "converged_at_start":
        assert k == 0
    else:
        assert k > 0
    # k CG iterations run k products; the warm start's r0 = b - A x0 is
    # the one product outside the loop.
    assert calls[0] == k + (1 if case == "warm" else 0)
