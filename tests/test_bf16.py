"""bf16 MXU pipeline (ISSUE 15): option gating, precision policy,
collective payload casts, escalation/serving composition, and the
slow-lane solve parity + guard-cleanliness contracts.

Tier-1 tests here are compile-free (option validation, policy casts on
eager scalars, fingerprint splits, the escalation rung transform), plus
one small single-device solve for the cost band and guard cleanliness;
the other solves and lowerings are slow-marked (tier-1 budget — see
ROADMAP).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from megba_tpu.common import (
    AlgoOption,
    PrecondKind,
    ProblemOption,
    RobustOption,
    SolverOption,
    validate_options,
)

BF16 = SolverOption(bf16=True)


def _opt(**kw):
    so = kw.pop("solver_option", BF16)
    kw.setdefault("dtype", np.float32)
    return ProblemOption(solver_option=so, **kw)


# ---------------------------------------------------------------------------
# Option gating (compile-free)
# ---------------------------------------------------------------------------

def test_bf16_refuses_f64_typed():
    with pytest.raises(ValueError, match="float64 problem asking for bf16"):
        validate_options(_opt(dtype=np.float64))


def test_bf16_collectives_requires_bf16():
    with pytest.raises(ValueError, match="requires SolverOption.bf16=True"):
        validate_options(_opt(
            solver_option=SolverOption(bf16_collectives=True)))


def test_bf16_refuses_mixed_precision_combo():
    with pytest.raises(ValueError, match="different rungs"):
        validate_options(_opt(mixed_precision_pcg=True))


def test_bf16_refuses_plain_solver():
    with pytest.raises(ValueError, match="only implemented for the Schur"):
        validate_options(_opt(use_schur=False))


def test_bf16_valid_configs_pass():
    validate_options(_opt())
    validate_options(_opt(solver_option=SolverOption(
        bf16=True, bf16_collectives=True), world_size=2))
    # composes with the 2-D mesh and every precond family's knobs
    validate_options(_opt(world_size=4, solver_option=SolverOption(
        bf16=True, bf16_collectives=True, mesh_2d=True, cam_blocks=2)))
    validate_options(_opt(solver_option=SolverOption(
        bf16=True, precond=PrecondKind.NEUMANN, neumann_order=1)))


def test_bf16_refuses_tiled_lowering_typed():
    # flat_solve refuses BEFORE any lowering — the tiled kernels have
    # no bf16 operand path and silently measuring f32 kernels under a
    # bf16 flag is exactly the silent-upcast failure mode.
    from megba_tpu.solve import flat_solve

    with pytest.raises(ValueError, match="bf16 does not compose"):
        flat_solve(lambda *a: None, np.zeros((2, 9), np.float32),
                   np.zeros((2, 3), np.float32), np.zeros((4, 2), np.float32),
                   np.zeros(4, np.int32), np.zeros(4, np.int32),
                   _opt(), use_tiled=True)


# ---------------------------------------------------------------------------
# Fingerprints / cache keys split for free (compile-free)
# ---------------------------------------------------------------------------

def test_bf16_joins_the_option_fingerprint():
    from megba_tpu.analysis.retrace import static_key

    base = _opt(solver_option=SolverOption())
    on = _opt()
    both = _opt(solver_option=SolverOption(bf16=True,
                                           bf16_collectives=True))
    keys = {static_key(o) for o in (base, on, both)}
    assert len(keys) == 3  # fleet bucket / artifact keys split for free


def test_bf16_rides_structured_option_config():
    from megba_tpu.observability.report import config_to_dict

    cfg = config_to_dict(_opt(solver_option=SolverOption(
        bf16=True, bf16_collectives=True)))
    assert cfg["solver_option"]["bf16"] is True
    assert cfg["solver_option"]["bf16_collectives"] is True


# ---------------------------------------------------------------------------
# Precision policy + payload casts (eager scalars, compile-free scale)
# ---------------------------------------------------------------------------

def test_edge_precision_modes():
    from megba_tpu.solver.pcg import _edge_precision, _ident

    up, vec, acc = _edge_precision(False, False)
    assert up is _ident and vec is _ident and acc is _ident
    up, vec, acc = _edge_precision(True, False)  # mixed: upcast rows
    assert up(jnp.ones((2,), jnp.bfloat16)).dtype == jnp.float32
    assert vec is _ident and acc is _ident
    up, vec, acc = _edge_precision(False, True)  # bf16 pipeline
    assert up is _ident
    assert vec(jnp.ones((2,), jnp.float32)).dtype == jnp.bfloat16
    assert acc(jnp.ones((2,), jnp.bfloat16)).dtype == jnp.float32


def test_collective_payload_cast_identity_when_off():
    from megba_tpu.parallel.mesh import collective_payload_cast

    down, up = collective_payload_cast(False)
    x = jnp.ones((3,), jnp.float32)
    assert down(x) is x and up(x) is x  # NO ops emitted: byte-identity
    down, up = collective_payload_cast(True)
    assert down(x).dtype == jnp.bfloat16
    assert up(down(x)).dtype == jnp.float32


def test_bf16_block_apply_accumulates_f32():
    from megba_tpu.solver.precond import (
        cam_block_matvec,
        cam_block_matvec_bf16,
    )

    rng = np.random.default_rng(0)
    H = jnp.asarray(rng.standard_normal((5, 4, 4)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 5)), jnp.float32)
    y32 = cam_block_matvec(H, x)
    yb = cam_block_matvec_bf16(H.astype(jnp.bfloat16), x)
    assert yb.dtype == jnp.float32
    rel = float(jnp.linalg.norm(yb - y32) / jnp.linalg.norm(y32))
    assert rel < 3e-2  # bf16-operand accuracy, not garbage


# ---------------------------------------------------------------------------
# Serving / escalation composition (compile-free)
# ---------------------------------------------------------------------------

def test_escalation_rung2_strips_bf16():
    from megba_tpu.serving.resilience import EscalationPolicy

    pol = EscalationPolicy()
    base = _opt(solver_option=SolverOption(bf16=True,
                                           bf16_collectives=True),
                robust_option=RobustOption())
    r1 = pol.option_for_rung(base, 1)
    assert r1.solver_option.bf16  # guards-only rung keeps the pipeline
    r2 = pol.option_for_rung(base, 2)
    assert not r2.solver_option.bf16
    assert not r2.solver_option.bf16_collectives


# ---------------------------------------------------------------------------
# Solves: the pipeline actually solves, guard-clean, at parity
# ---------------------------------------------------------------------------

def _scene():
    from megba_tpu.io.synthetic import make_synthetic_bal

    return make_synthetic_bal(
        num_cameras=8, num_points=60, obs_per_point=3, seed=0,
        param_noise=4e-2, pixel_noise=0.3, dtype=np.float32)


def _solve(s, **kw):
    from megba_tpu.ops.residuals import make_residual_jacobian_fn
    from megba_tpu.solve import flat_solve

    world = kw.pop("world", 1)
    mesh2d = kw.pop("mesh2d", False)
    forcing = kw.pop("forcing", True)
    lm = kw.pop("lm", 8)
    so = SolverOption(max_iter=kw.pop("max_iter", 100), forcing=forcing,
                      warm_start=forcing,
                      mesh_2d=mesh2d, cam_blocks=2 if mesh2d else 0, **kw)
    opt = ProblemOption(dtype=np.float32, world_size=world,
                        algo_option=AlgoOption(max_iter=lm),
                        solver_option=so,
                        robust_option=RobustOption(guards=True))
    f = make_residual_jacobian_fn()
    return flat_solve(f, s.cameras0, s.points0, s.obs, s.cam_idx,
                      s.pt_idx, opt, use_tiled=False)


def test_bf16_solve_parity_and_guard_clean():
    """The bf16 pipeline converges within the documented band of the
    f32 control with ZERO guard/recovery events — the acceptance
    contract, on the small scene."""
    s = _scene()
    r32 = _solve(s)
    rbf = _solve(s, bf16=True)
    gap = abs(float(rbf.cost) - float(r32.cost)) / float(r32.cost)
    assert gap <= 2e-2, gap
    assert int(rbf.recoveries) == 0
    it = int(rbf.iterations)
    assert int(np.asarray(rbf.trace.pcg_breakdown[:it]).sum()) == 0


@pytest.mark.slow
def test_bf16_collectives_world2_parity():
    s = _scene()
    r32 = _solve(s, world=2)
    rbf = _solve(s, world=2, bf16=True, bf16_collectives=True)
    gap = abs(float(rbf.cost) - float(r32.cost)) / float(r32.cost)
    assert gap <= 2e-2, gap
    assert int(rbf.recoveries) == 0


@pytest.mark.slow
def test_bf16_composes_with_2d_mesh():
    # Run to convergence (20 LM iters): the heavily-noised toy's
    # MID-trajectory costs wobble several % between summation
    # groupings (the 2-D bf16 operator regroups sums on top of the
    # rounding), while the converged basins agree at bf16-operator
    # accuracy — measured 1.6e-3 here; 8.9e-8 on a venice-10% CPU
    # scene.
    s = _scene()
    rbf = _solve(s, world=4, mesh2d=True, bf16=True, bf16_collectives=True,
                 lm=20)
    r32 = _solve(s, lm=20)
    gap = abs(float(rbf.cost) - float(r32.cost)) / float(r32.cost)
    assert gap <= 3e-2, gap
    assert int(rbf.recoveries) == 0


@pytest.mark.slow
def test_bf16_stagnation_exits_clean_not_broken():
    """Driving the bf16 inner solve far below its attainable floor
    (absolute tol 1e-8, refuse disabled) must STOP at the noise floor
    via the stagnation exit — best iterate restored, zero recoveries —
    instead of restart-thrashing into FATAL/RECOVERED."""
    from megba_tpu.common import SolveStatus

    s = _scene()
    r = _solve(s, bf16=True, forcing=False, tol=1e-8,
               refuse_ratio=1e30, max_iter=40)
    assert int(r.recoveries) == 0
    assert int(r.status) in (SolveStatus.MAX_ITER, SolveStatus.CONVERGED)
    assert np.isfinite(float(r.cost))
