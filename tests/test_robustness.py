"""Fault containment and recovery (megba_tpu/robustness/ + RobustOption).

Contract under test, in three layers:

- **Guards are free**: with `RobustOption(guards=True)` and nothing
  failing, the solve is BITWISE identical to the unguarded one (every
  guard is a select whose taken branch is the clean value).
- **Guards contain seeded faults**: a NaN residual burst and a
  Schur-indefiniteness burst each recover on-device
  (status=RECOVERED, final cost at the clean run's), while the same
  injection with guards off demonstrably poisons or degrades the solve
  — proving the guard, not luck, did the work.
- **Termination semantics**: LMResult.status partitions
  converged / max_iter / stalled / recovered / fatal_nonfinite, on
  device, consistently with the stop flag and accept counts.

One problem/config pair is shared across the module (compile-cache
friendly: each distinct program lowers once).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megba_tpu.common import (
    AlgoOption,
    JacobianMode,
    ProblemOption,
    PreconditionerKind,
    RobustOption,
    SolverOption,
    SolveStatus,
    status_name,
    validate_options,
)
from megba_tpu.io.synthetic import make_synthetic_bal
from megba_tpu.ops.residuals import make_residual_jacobian_fn
from megba_tpu.robustness.faults import (
    FaultPlan,
    lower_edge_vector,
    make_nan_burst,
    make_point_indefinite_burst,
    with_offset,
)
from megba_tpu.solve import flat_solve


@pytest.fixture(scope="module")
def problem():
    s = make_synthetic_bal(num_cameras=6, num_points=40, obs_per_point=4,
                           seed=1, param_noise=4e-2, pixel_noise=0.3)
    option = ProblemOption(
        algo_option=AlgoOption(max_iter=12, epsilon1=1e-9, epsilon2=1e-12),
        solver_option=SolverOption(max_iter=100, tol=1e-13,
                                   refuse_ratio=1e30))
    f = make_residual_jacobian_fn(mode=JacobianMode.ANALYTICAL)
    return s, option, f


def _args(s, f):
    return (f, s.cameras0, s.points0, s.obs, s.cam_idx, s.pt_idx)


def _guarded(option, **kw):
    return dataclasses.replace(
        option, robust_option=RobustOption(guards=True, **kw))


@pytest.fixture(scope="module")
def clean_off(problem):
    s, option, f = problem
    return flat_solve(*_args(s, f), option)


@pytest.fixture(scope="module")
def clean_on(problem):
    s, option, f = problem
    return flat_solve(*_args(s, f), _guarded(option))


@pytest.fixture(scope="module")
def nan_plan(problem):
    s, _, _ = problem
    # Burst covering iteration 0: poisons the initial linearisation too,
    # so the guard-off baseline carries a non-finite cost forever.
    return make_nan_burst(s.obs.shape[0], [2, 9], start=0, stop=1)


# ------------------------------------------------------------------ free


def test_clean_run_bitwise_unchanged_with_guards(clean_off, clean_on):
    assert np.array_equal(np.asarray(clean_off.cameras),
                          np.asarray(clean_on.cameras))
    assert np.array_equal(np.asarray(clean_off.points),
                          np.asarray(clean_on.points))
    assert (np.asarray(clean_off.cost).tobytes()
            == np.asarray(clean_on.cost).tobytes())
    assert int(clean_on.recoveries) == 0
    assert int(clean_off.status) == int(clean_on.status)
    # And the guarded trace recorded no fault events.
    it = int(clean_on.iterations)
    assert not np.asarray(clean_on.trace.recovery)[:it].any()
    assert not np.asarray(clean_on.trace.pcg_breakdown)[:it].any()


# ------------------------------------------------------- NaN residual burst


def test_nan_burst_poisons_unguarded_solve(problem, nan_plan):
    s, option, f = problem
    res = flat_solve(*_args(s, f), option, fault_plan=nan_plan)
    assert not np.isfinite(float(res.cost))
    # Nothing was ever accepted against a NaN carried cost: stalled.
    assert int(res.status) == SolveStatus.STALLED
    assert int(res.accepted) == 0


def test_nan_burst_recovers_with_guards(problem, nan_plan, clean_off):
    s, option, f = problem
    res = flat_solve(*_args(s, f), _guarded(option), fault_plan=nan_plan)
    assert int(res.status) == SolveStatus.RECOVERED
    assert int(res.recoveries) >= 1
    assert np.isfinite(float(res.cost))
    np.testing.assert_allclose(float(res.cost), float(clean_off.cost),
                               rtol=1e-4)
    it = int(res.iterations)
    rec = np.asarray(res.trace.recovery)[:it]
    assert rec[:2].any() and not rec[2:].any()


def test_nan_burst_world2_matches_single_device(problem, nan_plan):
    s, option, f = problem
    single = flat_solve(*_args(s, f), _guarded(option), fault_plan=nan_plan)
    w2 = flat_solve(*_args(s, f),
                    dataclasses.replace(_guarded(option), world_size=2),
                    fault_plan=nan_plan)
    assert int(w2.status) == SolveStatus.RECOVERED
    assert int(w2.recoveries) == int(single.recoveries)
    # Reduction order, not a fault-path difference: world 2 sums each
    # shard and then psums, so the costs agree to ~1e-15 through the
    # recovery and for three iterations after it.  From there the PCG
    # (tol 1e-13) stops a few iterations apart on the two sides (46 vs
    # 50 at LM iteration 10 on jax 0.9), and the solve ends at max_iter
    # still converging, so the ulp-level gap shows as ~9e-10 at the end.
    np.testing.assert_allclose(float(w2.cost), float(single.cost),
                               rtol=1e-8)


def test_fault_injection_is_deterministic(problem, nan_plan):
    s, option, f = problem
    a = flat_solve(*_args(s, f), _guarded(option), fault_plan=nan_plan)
    b = flat_solve(*_args(s, f), _guarded(option), fault_plan=nan_plan)
    assert np.array_equal(np.asarray(a.cameras), np.asarray(b.cameras))
    assert np.array_equal(np.asarray(a.points), np.asarray(b.points))
    assert float(a.cost) == float(b.cost)


def test_fatal_after_max_recoveries(problem):
    s, option, f = problem
    # Persistent fault: every recovery relinearisation is poisoned too,
    # so the streak can only grow.  Default RobustOption keeps this on
    # the same compiled program as the transient-burst tests (the plan
    # is a dynamic operand).
    plan = make_nan_burst(s.obs.shape[0], [2], start=0, stop=10_000)
    res = flat_solve(*_args(s, f), _guarded(option), fault_plan=plan)
    assert int(res.status) == SolveStatus.FATAL_NONFINITE
    # Bailed after max_recoveries+1 consecutive failures, not max_iter.
    assert int(res.iterations) == RobustOption().max_recoveries + 1
    assert bool(res.stopped)


# ------------------------------------------- Schur-indefiniteness breakdown


def test_indefinite_fault_triggers_pcg_breakdown_and_recovery(
        problem, clean_off):
    s, option, f = problem
    plan = make_point_indefinite_burst(
        40, list(range(8)), start=2, stop=3, n_edges=s.obs.shape[0])
    res = flat_solve(*_args(s, f), _guarded(option), fault_plan=plan)
    it = int(res.iterations)
    breakdowns = np.asarray(res.trace.pcg_breakdown)[:it]
    # The guard restarted (bounded) inside the jitted PCG body, then the
    # LM guard rolled the step back and relinearised.
    assert breakdowns.sum() >= 1
    assert np.asarray(res.trace.recovery)[:it].any()
    assert int(res.status) == SolveStatus.RECOVERED
    np.testing.assert_allclose(float(res.cost), float(clean_off.cost),
                               rtol=1e-6)


def test_pcg_core_guard_is_bitwise_free_and_flags_indefinite():
    from megba_tpu.solver.pcg import _pcg_core

    rng = np.random.default_rng(0)
    a = rng.standard_normal((12, 12))
    spd = jnp.asarray(a @ a.T + 12 * np.eye(12))
    b = jnp.asarray(rng.standard_normal(12))

    def run(mat, guard, max_restarts=0):
        return _pcg_core(lambda x: mat @ x, lambda r: r, b, 50, 1e-12,
                         1e30, False, guard=guard,
                         max_restarts=max_restarts)

    x0, k0, rho0, _, re0, br0 = run(spd, False)
    x1, k1, rho1, _, re1, br1 = run(spd, True, max_restarts=2)
    assert np.array_equal(np.asarray(x0), np.asarray(x1))
    assert int(k0) == int(k1)
    assert float(rho0) == float(rho1)
    assert int(re1) == 0 and not bool(br1)

    # Indefinite operator: delta = <p, A p> flips sign -> breakdown;
    # restarts cannot cure an indefinite matrix, so the guard exits
    # flagged after the bounded budget instead of silently iterating.
    indef = jnp.asarray(a @ a.T - 30 * np.eye(12))
    _, _, _, _, re2, br2 = run(indef, True, max_restarts=2)
    assert bool(br2)
    assert int(re2) == 2


def test_pcg_core_guard_restart_converges_to_dense_answer():
    """One poisoned product mid-solve: the guarded body restarts once
    (residual refresh, then a first CG step from the refreshed residual)
    and still converges to the dense solution, one product per trip."""
    from jax.experimental import io_callback

    from megba_tpu.solver.pcg import _pcg_core

    rng = np.random.default_rng(1)
    a = rng.standard_normal((12, 12))
    spd = np.asarray(a @ a.T + 12 * np.eye(12))
    b = rng.standard_normal(12)
    calls = [0]

    def scale():
        # The third product comes back NaN: its delta = <u, A u> is
        # non-finite, a breakdown.
        calls[0] += 1
        return np.float64(np.nan if calls[0] == 3 else 1.0)

    def matvec(v):
        return (jnp.asarray(spd) @ v) * io_callback(
            scale, jax.ShapeDtypeStruct((), jnp.float64))

    x, k, rho, _, restarts, broken = _pcg_core(
        matvec, lambda r: r, jnp.asarray(b), 60, 1e-20, 1e30, False,
        guard=True, max_restarts=2)
    jax.effects_barrier()
    assert int(restarts) == 1 and not bool(broken)
    assert abs(float(rho)) < 1e-20
    assert calls[0] == int(k)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(spd, b),
                               rtol=1e-9, atol=1e-12)


# ----------------------------------------------- preconditioner fallback


def test_schur_diag_precond_fallback_is_counted():
    from megba_tpu.solver.pcg import _schur_diag_precond, block_inv
    from megba_tpu.common import ComputeKind

    # Two cameras, one point, two edges (one per camera).  Camera 0's
    # correction overwhelms its Hpp block (huge Hll^-1) -> indefinite
    # Schur diagonal -> Cholesky NaN -> counted fallback to Hpp.
    cd, pd = 2, 2
    Hpp_d = jnp.asarray(np.stack([np.eye(cd), 4 * np.eye(cd)]),
                        jnp.float64)
    Hll_inv = jnp.asarray(
        np.tile(np.eye(pd).reshape(pd * pd, 1), (1, 1)) * 1e6, jnp.float64)
    W = jnp.asarray(
        np.stack([np.array([1.0, 0.0]), np.array([0.0, 0.0]),
                  np.array([0.0, 0.0]), np.array([0.0, 0.0])]),
        jnp.float64)  # [cd*pd, nE]: only camera 0's edge couples
    cam_idx = jnp.asarray(np.array([0, 1], np.int32))
    pt_idx = jnp.asarray(np.zeros(2, np.int32))
    minv, n_bad = _schur_diag_precond(
        Hpp_d, Hll_inv, W, None, None, cam_idx, pt_idx, 2,
        ComputeKind.EXPLICIT, None, False)
    assert int(n_bad) == 1
    # The fallen-back block IS the Hpp preconditioner; the healthy
    # block keeps the true Schur diagonal.
    np.testing.assert_allclose(np.asarray(minv)[0],
                               np.asarray(block_inv(Hpp_d))[0])
    assert np.isfinite(np.asarray(minv)).all()


def test_precond_fallback_surfaces_in_trace(problem):
    s, option, f = problem
    opt = dataclasses.replace(
        _guarded(option),
        solver_option=dataclasses.replace(
            option.solver_option,
            preconditioner=PreconditionerKind.SCHUR_DIAG))
    plan = make_point_indefinite_burst(
        40, list(range(8)), start=2, stop=3, n_edges=s.obs.shape[0])
    res = flat_solve(*_args(s, f), opt, fault_plan=plan)
    it = int(res.iterations)
    fallbacks = np.asarray(res.trace.precond_fallback)[:it]
    # The crushed Hll blocks make the Schur diagonal of the cameras
    # seeing them indefinite -> the Cholesky-NaN fallback fires and is
    # COUNTED per iteration instead of being silent.
    assert fallbacks.sum() >= 1


# ------------------------------------------------------------ semantics


def test_status_consistent_with_stop_flag(clean_off, clean_on):
    for res in (clean_off, clean_on):
        want = (SolveStatus.CONVERGED if bool(res.stopped)
                else (SolveStatus.MAX_ITER if int(res.accepted) > 0
                      else SolveStatus.STALLED))
        assert int(res.status) == want


def test_status_names():
    assert status_name(SolveStatus.RECOVERED) == "recovered"
    assert status_name(4) == "fatal_nonfinite"
    assert status_name(99) == "unknown(99)"


def test_robust_option_validation():
    base = ProblemOption()
    with pytest.raises(ValueError, match="max_recoveries"):
        validate_options(dataclasses.replace(
            base, robust_option=RobustOption(max_recoveries=0)))
    with pytest.raises(ValueError, match="damping_inflation"):
        validate_options(dataclasses.replace(
            base, robust_option=RobustOption(damping_inflation=1.0)))
    with pytest.raises(ValueError, match="pcg_max_restarts"):
        validate_options(dataclasses.replace(
            base, robust_option=RobustOption(pcg_max_restarts=-1)))


def test_fault_plan_size_mismatch_rejected(problem):
    s, option, f = problem
    plan = make_nan_burst(3, [0], start=0, stop=1)
    with pytest.raises(ValueError, match="edge_nan"):
        flat_solve(*_args(s, f), option, fault_plan=plan)


def test_lower_edge_vector_never_multiplies_nan_into_padding():
    vec = np.array([np.nan, 0.0, np.nan, 0.0])
    perm = np.array([2, 0, 1, 3, 0, 0])  # padded perm reuses real rows
    mask = np.array([1.0, 1.0, 1.0, 1.0, 0.0, 0.0])
    out = lower_edge_vector(vec, perm, mask, n_padded=8)
    assert out.shape == (8,)
    assert np.isnan(out[:2]).all()
    assert (out[3:] == 0).all()  # masked + padded slots are exact zeros


def test_with_offset_slides_window():
    plan = make_nan_burst(4, [1], start=3, stop=5)
    moved = with_offset(plan, 3)
    assert int(moved.offset) == 3
    assert isinstance(moved, FaultPlan)
    np.testing.assert_array_equal(moved.window, plan.window)


# --------------------------------------------------- chunk-resume fault


def test_resume_relinearization_fault_contained(problem, tmp_path):
    """The preemption story end to end: a transient fault hits exactly
    the resumed chunk's initial relinearisation (global iteration 3).
    Guards off, the resumed chunk's carried cost is non-finite for good;
    guards on, the solve recovers and lands on the clean chunked cost."""
    from megba_tpu.algo.checkpointed import solve_checkpointed

    s, option, f = problem
    args = (f, np.asarray(s.cameras0), np.asarray(s.points0),
            np.asarray(s.obs), np.asarray(s.cam_idx), np.asarray(s.pt_idx))
    short = dataclasses.replace(
        option,
        algo_option=dataclasses.replace(option.algo_option, max_iter=3))
    plan = make_nan_burst(s.obs.shape[0], [2, 9], start=3, stop=4)

    def two_phase(opt, name, fault=None):
        ck = str(tmp_path / f"{name}.npz")
        solve_checkpointed(
            *args, dataclasses.replace(
                short, robust_option=opt.robust_option),
            checkpoint_path=ck, checkpoint_every=3)
        kw = {} if fault is None else {"fault_plan": fault}
        return solve_checkpointed(*args, opt, checkpoint_path=ck,
                                  checkpoint_every=20, **kw)

    clean = two_phase(option, "clean")
    off = two_phase(option, "off", plan)
    assert not np.isfinite(float(off.cost))
    on = two_phase(_guarded(option), "on", plan)
    assert int(on.status) == SolveStatus.RECOVERED
    assert int(on.recoveries) >= 1
    np.testing.assert_allclose(float(on.cost), float(clean.cost),
                               rtol=1e-5)
    # The stitched trace marks the recovery at the resume point.
    rec = np.asarray(on.trace.recovery)
    assert rec[3:5].any()


def test_resume_after_fatal_stays_fatal(problem, tmp_path):
    """Fatality is sticky across a snapshot resume: the snapshot records
    the fatal bail-out, so a rerun over the same checkpoint must report
    FATAL_NONFINITE again — not re-derive recovered/converged from the
    evaluate-only resume chunk."""
    from megba_tpu.algo.checkpointed import solve_checkpointed

    s, option, f = problem
    args = (f, np.asarray(s.cameras0), np.asarray(s.points0),
            np.asarray(s.obs), np.asarray(s.cam_idx), np.asarray(s.pt_idx))
    # Persistent fault: every recovery relinearisation is poisoned too,
    # so the first chunk exhausts max_recoveries and bails fatal.
    plan = make_nan_burst(s.obs.shape[0], [2], start=0, stop=10_000)
    ck = str(tmp_path / "fatal.npz")
    first = solve_checkpointed(*args, _guarded(option), checkpoint_path=ck,
                               checkpoint_every=20, fault_plan=plan)
    assert int(first.status) == SolveStatus.FATAL_NONFINITE
    resumed = solve_checkpointed(*args, _guarded(option), checkpoint_path=ck,
                                 checkpoint_every=20, fault_plan=plan)
    assert int(resumed.status) == SolveStatus.FATAL_NONFINITE
