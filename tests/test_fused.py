"""Fused edge-pipeline mega-kernels (megba_tpu/ops/fused.py).

Three layers of coverage:

- COMPILE-FREE units (tier-1): bucket-plan invariants, the option's
  identity-lane membership (fingerprint / static key), validate_options
  and flat_solve refusal arms BOTH ways, and the escalation rung-2
  strip — everything that must hold without tracing a program.
- KERNEL PARITY (slow): every fused kernel in Pallas interpret mode —
  the CPU-lane certificate — against the plain-XLA gather/contract/
  scatter oracle, f32/f64/bf16, explicit and implicit, 1-D bucket
  plans and the 2-D single-block ring step, plus the fused M⁻¹ apply.
  The bf16 arm additionally asserts the f32-accumulator contract at
  the kernel's OUTPUT dtype (the in-kernel trace assert in
  `_contract_rows` fires under interpret mode too).
- END-TO-END: flat_solve fused-on vs fused-off LM cost parity at the
  pinned tolerance (tier-1 on the default implicit config; slow for
  the explicit arm, the newly-legal tiled+bf16 arm and the 2-D mesh
  composition).
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
    SolverOption,
    validate_options,
)
from megba_tpu.ops import fused
from megba_tpu.ops.fused import (
    FusedPlan,
    build_fused_dual_plans,
    build_fused_plan,
    device_fused_plan,
    fused_block_diag_apply,
    fused_coupling_apply,
    fused_coupling_apply_implicit,
    fused_plan_summary,
    fused_single_block_apply,
    permute_rows,
    reference_coupling_apply,
    reference_coupling_apply_implicit,
)


def _graph(ne=400, ni=40, no=90, seed=0, with_mask=True):
    rng = np.random.default_rng(seed)
    in_idx = rng.integers(0, ni, ne).astype(np.int32)
    out_idx = rng.integers(0, no, ne).astype(np.int32)
    mask = None
    if with_mask:
        mask = (rng.random(ne) > 0.1).astype(np.float32)
    return in_idx, out_idx, mask


def _check_plan_invariants(plan: FusedPlan, in_idx, out_idx, mask):
    real = plan.mask > 0
    n_real = int((mask > 0).sum()) if mask is not None else in_idx.shape[0]
    # Every unmasked source edge routed exactly once; padding zeroed.
    assert plan.n_edges == n_real
    assert int(real.sum()) == n_real
    src = np.nonzero(mask > 0)[0] if mask is not None else np.arange(
        in_idx.shape[0])
    assert np.array_equal(np.sort(plan.perm[real]), np.sort(src))
    assert plan.n_slots == plan.n_tiles * plan.tile
    # Per-slot locals match the source indices, block-local.
    slot_tile = np.repeat(np.arange(plan.n_tiles), plan.tile)
    assert np.array_equal(
        plan.in_local[real],
        (in_idx[plan.perm[real]] % plan.in_block).astype(np.int32))
    assert np.array_equal(
        plan.out_local[real],
        (out_idx[plan.perm[real]] % plan.out_block).astype(np.int32))
    # Every slot's GLOBAL segment lands in its tile's declared blocks.
    assert np.array_equal(
        in_idx[plan.perm[real]] // plan.in_block,
        plan.tile_in[slot_tile[real]])
    assert np.array_equal(
        out_idx[plan.perm[real]] // plan.out_block,
        plan.tile_out[slot_tile[real]])
    # Output-block visits are CONTIGUOUS runs (the sequential-
    # accumulation contract) with first-flags on every transition...
    changes = np.nonzero(plan.tile_out[1:] != plan.tile_out[:-1])[0]
    visited_runs = changes.size + 1
    assert visited_runs == np.unique(plan.tile_out).size
    want_first = np.zeros(plan.n_tiles, np.int32)
    want_first[0] = 1
    want_first[changes + 1] = 1
    assert np.array_equal(plan.tile_first, want_first)
    # ...and EVERY output block gets at least one (tail) tile, so the
    # kernel initialises the whole output buffer.
    assert np.array_equal(np.unique(plan.tile_out),
                          np.arange(plan.num_out_blocks))


# ---------------------------------------------------------------------------
# Tier-1: plan + option units (no kernel compilation)
# ---------------------------------------------------------------------------

def test_fused_plan_invariants():
    in_idx, out_idx, mask = _graph()
    plan = build_fused_plan(in_idx, out_idx, mask, 40, 90,
                            tile=16, in_block=16, out_block=32)
    _check_plan_invariants(plan, in_idx, out_idx, mask)
    assert 0.0 < plan.occupancy <= 1.0


def test_fused_plan_no_mask_and_edgeless_blocks():
    # Half the output blocks have no edges at all: they must still be
    # covered by all-padding tail tiles (zero-init, not garbage).
    in_idx, out_idx, _ = _graph(ne=64, ni=8, no=30, with_mask=False)
    out_idx = (out_idx % 7).astype(np.int32)  # blocks past 7 edgeless
    plan = build_fused_plan(in_idx, out_idx, None, 8, 30,
                            tile=8, in_block=8, out_block=4)
    _check_plan_invariants(plan, in_idx, out_idx, None)
    assert plan.num_out_blocks == 8


def test_fused_dual_plans_directions():
    cam_idx, pt_idx, mask = _graph(ne=300, ni=12, no=70, seed=3)
    fp_tp, fp_tc, dfp_tp, dfp_tc = build_fused_dual_plans(
        cam_idx, pt_idx, mask, 12, 70, tile=16, block_cam=8, block_pt=16)
    _check_plan_invariants(fp_tp, cam_idx, pt_idx, mask)
    _check_plan_invariants(fp_tc, pt_idx, cam_idx, mask)
    assert fp_tp.num_out_segments == 70 and fp_tc.num_out_segments == 12
    # Device halves are pytrees: flattenable, index arrays as leaves.
    leaves = jax.tree_util.tree_leaves(dfp_tp)
    assert len(leaves) == 7
    s = fused_plan_summary(fp_tp)
    assert set(s) == {"tiles", "tile", "occupancy", "edges", "slots"}
    assert s["edges"] == fp_tp.n_edges


def test_validate_options_refuses_fused_without_schur():
    opt = ProblemOption(use_schur=False, solver_option=SolverOption(
        fused_kernels=True))
    with pytest.raises(ValueError, match="fused_kernels"):
        validate_options(opt)
    validate_options(ProblemOption(solver_option=SolverOption(
        fused_kernels=True)))  # Schur path: legal


def test_fused_kernels_joins_option_fingerprint():
    # The serving fingerprint / bucket key is static_key(engine, option)
    # over the whole frozen option repr: toggling the flag MUST change
    # it (same-key artifacts would alias two different programs).
    from megba_tpu.analysis.retrace import static_key

    off = ProblemOption()
    on = dataclasses.replace(off, solver_option=dataclasses.replace(
        off.solver_option, fused_kernels=True))
    k_off, k_on = static_key(None, off), static_key(None, on)
    assert k_off != k_on
    assert "fused_kernels=True" in k_on
    assert "fused_kernels=False" in k_off


def test_rung2_strips_fused_kernels():
    from megba_tpu.serving.resilience import EscalationPolicy

    policy = EscalationPolicy()
    opt = ProblemOption(solver_option=SolverOption(fused_kernels=True))
    assert policy.option_for_rung(opt, 1).solver_option.fused_kernels \
        is True
    for rung in (2, 3):
        stripped = policy.option_for_rung(opt, rung)
        assert stripped.solver_option.fused_kernels is False


def _ba(nc=6, npts=40, dtype=np.float32):
    from megba_tpu.io.synthetic import make_synthetic_bal

    return make_synthetic_bal(
        num_cameras=nc, num_points=npts, obs_per_point=3, seed=0,
        param_noise=4e-2, pixel_noise=0.3, dtype=dtype)


def _solve(s, option, use_tiled=None, **kw):
    from megba_tpu.ops.residuals import make_residual_jacobian_fn
    from megba_tpu.solve import flat_solve

    f = make_residual_jacobian_fn(mode=JacobianMode.ANALYTICAL)
    return flat_solve(f, s.cameras0, s.points0, s.obs, s.cam_idx,
                      s.pt_idx, option, use_tiled=use_tiled, **kw)


def _opt(fused_kernels=False, bf16=False, **kw):
    return ProblemOption(
        dtype=np.float32,
        algo_option=AlgoOption(max_iter=4),
        solver_option=SolverOption(max_iter=12, tol=1e-8,
                                   fused_kernels=fused_kernels,
                                   bf16=bf16, **kw))


def test_flat_solve_refusal_arms():
    s = _ba()
    # fused + explicit non-tiled: refused typed, naming the knobs.
    with pytest.raises(ValueError, match="tiled edge plans"):
        _solve(s, _opt(fused_kernels=True), use_tiled=False)
    # fused + 1-D multi-device: refused typed, naming mesh_2d.
    opt_w2 = dataclasses.replace(_opt(fused_kernels=True), world_size=2)
    with pytest.raises(ValueError, match="mesh_2d=True"):
        _solve(s, opt_w2)
    # bf16 + explicit tiled WITHOUT fused: still refused — and the
    # error must name the fused alternative that makes it legal.
    with pytest.raises(ValueError, match="fused_kernels=True"):
        _solve(s, _opt(bf16=True), use_tiled=True)


# ---------------------------------------------------------------------------
# Kernel parity (interpret mode = the CPU-lane certificate)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("w_in_major", [True, False])
def test_fused_explicit_parity(dtype, w_in_major):
    rng = np.random.default_rng(1)
    in_idx, out_idx, mask = _graph(ne=500, ni=30, no=80, seed=1)
    d_in, d_out = (9, 3) if w_in_major else (3, 9)
    plan = build_fused_plan(in_idx, out_idx, mask, 30, 80,
                            tile=32, in_block=16, out_block=32)
    dplan = device_fused_plan(plan)
    W = jnp.asarray(rng.standard_normal((27, 500)), dtype) * jnp.asarray(
        mask, dtype)
    table = jnp.asarray(rng.standard_normal((d_in, 30)), dtype)
    got = fused_coupling_apply(permute_rows(W, dplan), table, dplan,
                               w_in_major=w_in_major, interpret=True)
    want = reference_coupling_apply(W, table, in_idx, out_idx, 80,
                                    w_in_major, d_in)
    tol = 1e-6 if dtype == np.float32 else 1e-12
    assert got.dtype == want.dtype == dtype
    err = float(jnp.max(jnp.abs(got - want))
                / (1.0 + jnp.max(jnp.abs(want))))
    assert err < tol


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_fused_implicit_parity(dtype):
    rng = np.random.default_rng(2)
    in_idx, out_idx, mask = _graph(ne=500, ni=30, no=80, seed=2)
    od, d_in, d_out = 2, 9, 3
    plan = build_fused_plan(in_idx, out_idx, mask, 30, 80,
                            tile=32, in_block=16, out_block=32)
    dplan = device_fused_plan(plan)
    m = jnp.asarray(mask, dtype)
    Jin = jnp.asarray(rng.standard_normal((od * d_in, 500)), dtype) * m
    Jout = jnp.asarray(rng.standard_normal((od * d_out, 500)), dtype) * m
    table = jnp.asarray(rng.standard_normal((d_in, 30)), dtype)
    got = fused_coupling_apply_implicit(
        permute_rows(Jin, dplan), permute_rows(Jout, dplan), table, dplan,
        interpret=True)
    want = reference_coupling_apply_implicit(Jin, Jout, table, in_idx, out_idx, 80, d_in)
    tol = 1e-5 if dtype == np.float32 else 1e-12
    err = float(jnp.max(jnp.abs(got - want))
                / (1.0 + jnp.max(jnp.abs(want))))
    assert err < tol


@pytest.mark.slow
def test_fused_bf16_accumulates_in_f32():
    # The precision-contract certificate: bf16 operand tiles, f32
    # accumulator — the kernel's OUTPUT dtype is the accumulator dtype
    # (the trace-time assert inside `_contract_rows` enforces the
    # in-kernel dtype; interpret mode runs the same trace).
    rng = np.random.default_rng(3)
    in_idx, out_idx, mask = _graph(ne=400, ni=20, no=60, seed=4)
    plan = build_fused_plan(in_idx, out_idx, mask, 20, 60,
                            tile=32, in_block=16, out_block=32)
    dplan = device_fused_plan(plan)
    W = jnp.asarray(rng.standard_normal((27, 400)), jnp.bfloat16)
    W = W * jnp.asarray(mask, jnp.bfloat16)
    table = jnp.asarray(rng.standard_normal((9, 20)), np.float32)
    got = fused_coupling_apply(permute_rows(W, dplan), table, dplan,
                               w_in_major=True, bf16_operands=True,
                               interpret=True)
    assert got.dtype == jnp.float32  # f32 accumulation, not bf16
    want = reference_coupling_apply(
        W.astype(np.float32), table, in_idx, out_idx, 60, True, 9)
    err = float(jnp.max(jnp.abs(got - want))
                / (1.0 + jnp.max(jnp.abs(want))))
    assert err < 3e-2  # bf16 operand rounding, f32 accumulation


@pytest.mark.slow
def test_fused_block_diag_parity():
    rng = np.random.default_rng(5)
    for dtype, tol in ((np.float32, 1e-6), (np.float64, 1e-13)):
        Minv = jnp.asarray(rng.standard_normal((17, 9, 9)), dtype)
        x = jnp.asarray(rng.standard_normal((9, 17)), dtype)
        Hrows = fused.block_diag_rows(Minv)
        got = fused_block_diag_apply(Hrows, x, interpret=True)
        want = jnp.einsum("cij,jc->ic", Minv, x)
        assert got.dtype == x.dtype
        err = float(jnp.max(jnp.abs(got - want))
                    / (1.0 + jnp.max(jnp.abs(want))))
        assert err < tol


@pytest.mark.slow
def test_fused_single_block_ring_step_parity():
    # The 2-D mesh ring-step contraction: one input block (the rotating
    # point shard), one output block (the camera tile).
    rng = np.random.default_rng(6)
    ne, n_in, n_out = 256, 16, 8
    in_local = jnp.asarray(rng.integers(0, n_in, ne), jnp.int32)
    out_local = jnp.asarray(rng.integers(0, n_out, ne), jnp.int32)
    W = jnp.asarray(rng.standard_normal((27, ne)), np.float32)
    table = jnp.asarray(rng.standard_normal((3, n_in)), np.float32)
    got = fused_single_block_apply(W, table, in_local, out_local,
                                   out_block=n_out, w_in_major=False,
                                   interpret=True)
    want = reference_coupling_apply(
        W, table, np.asarray(in_local), np.asarray(out_local), n_out,
        False, 3)
    err = float(jnp.max(jnp.abs(got - want))
                / (1.0 + jnp.max(jnp.abs(want))))
    assert err < 1e-6
    # Implicit two-stage arm.
    Jin = jnp.asarray(rng.standard_normal((2 * 3, ne)), np.float32)
    Jout = jnp.asarray(rng.standard_normal((2 * 9, ne)), np.float32)
    got = fused_single_block_apply(Jin, table, in_local, out_local,
                                   out_block=n_out, rows_out=Jout,
                                   interpret=True)
    want = reference_coupling_apply_implicit(Jin, Jout, table, np.asarray(in_local),
                               np.asarray(out_local), n_out, 3)
    err = float(jnp.max(jnp.abs(got - want))
                / (1.0 + jnp.max(jnp.abs(want))))
    assert err < 1e-5


# ---------------------------------------------------------------------------
# End-to-end LM parity (the acceptance pins)
# ---------------------------------------------------------------------------

def _rel_gap(a, b):
    return abs(float(a) - float(b)) / max(1.0, abs(float(b)))


def test_flat_solve_fused_cost_parity_tiled():
    s = _ba()
    base = _solve(s, _opt(), use_tiled=True)
    fused_res = _solve(s, _opt(fused_kernels=True))
    assert _rel_gap(fused_res.cost, base.cost) < 1e-5
    assert fused_res.cost < base.initial_cost  # actually converged


@pytest.mark.slow
def test_flat_solve_fused_cost_parity_explicit_compute():
    # EXPLICIT W-contraction arm at the default short-LM config.  The
    # two arms reduce the same edge products in different orders, so
    # after a few accept/reject branch points the f32 trajectories sit
    # ~1e-5 apart — pure ordering noise, not kernel error (single-kernel
    # parity is pinned at 1e-6 above; the strict <=1e-5 end-to-end pin
    # rides the default IMPLICIT config in
    # test_flat_solve_fused_cost_parity_tiled).  Longer LM runs only widen the branch
    # divergence, so the band here is 5e-5 at the short config.
    from megba_tpu.common import ComputeKind

    s = _ba()
    base = _solve(s, dataclasses.replace(
        _opt(), compute_kind=ComputeKind.EXPLICIT), use_tiled=True)
    fused_res = _solve(s, dataclasses.replace(
        _opt(fused_kernels=True), compute_kind=ComputeKind.EXPLICIT))
    assert _rel_gap(fused_res.cost, base.cost) < 5e-5


@pytest.mark.slow
def test_flat_solve_fused_lifts_bf16_tiled_refusal():
    # The satellite pin: tiled+bf16 is refused without fused_kernels
    # (asserted compile-free above) and LEGAL with it — and the result
    # must sit in the bf16 band of the XLA bf16 lowering, not at it
    # bit-for-bit (different operand orderings).
    s = _ba()
    fused_res = _solve(s, _opt(fused_kernels=True, bf16=True),
                       use_tiled=True)
    xla = _solve(s, _opt(bf16=True), use_tiled=False)
    assert fused_res.cost < fused_res.initial_cost
    # Both arms converge to the same decade (bf16 operand rounding).
    assert _rel_gap(fused_res.cost, xla.cost) < 0.5


@pytest.mark.slow
def test_flat_solve_fused_mesh2d_parity():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (virtual CPU mesh)")
    s = _ba(nc=8, npts=48)
    opt = dataclasses.replace(
        _opt(), world_size=4,
        solver_option=dataclasses.replace(
            _opt().solver_option, mesh_2d=True, cam_blocks=2))
    base = _solve(s, opt, use_tiled=False)
    opt_f = dataclasses.replace(
        opt, solver_option=dataclasses.replace(
            opt.solver_option, fused_kernels=True))
    fused_res = _solve(s, opt_f, use_tiled=False)
    assert _rel_gap(fused_res.cost, base.cost) < 1e-5


@pytest.mark.slow
def test_fused_report_carries_tile_metrics(tmp_path, monkeypatch):
    # SolveReport.tiles: the reuse/occupancy metrics plus per-direction
    # fused plan summaries, rendered by summarize without error.
    import json as _json

    path = tmp_path / "t.jsonl"
    monkeypatch.setenv("MEGBA_TELEMETRY", str(path))
    s = _ba()
    _solve(s, _opt(fused_kernels=True))
    lines = path.read_text().strip().splitlines()
    doc = _json.loads(lines[-1])
    tiles = doc["tiles"]
    assert tiles["plan"] == "tiled_1d"
    assert "reuse_factor" in tiles and "occupancy" in tiles
    assert tiles["fused_to_pt"]["edges"] > 0
    assert tiles["fused_to_cam"]["slots"] >= tiles["fused_to_cam"]["edges"]
    from megba_tpu.observability.report import SolveReport
    from megba_tpu.observability.summarize import format_report

    text = format_report(SolveReport.from_json(lines[-1]))
    assert "tiles[tiled_1d]" in text
    assert "fused_to_pt" in text


def test_fused_kernel_check_interpret_mode():
    """fused.check_kernels (the chip's fused-kernel gate) at the
    production geometry through the Pallas interpreter: both explicit
    directions, both implicit directions and the M^-1 apply."""
    from megba_tpu.ops.segtiles import KERNEL_CHECK_TOL

    results = fused.check_kernels(interpret=True)
    assert len(results) == 5
    assert all(err <= KERNEL_CHECK_TOL for _, _, err in results)
