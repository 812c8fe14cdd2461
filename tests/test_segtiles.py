"""Block-aligned tiled segment reduction: plan invariants + kernel parity.

The kernels are the replacement for the per-edge scatter/gather of the
reference's assembly/SpMV path (build_linear_system.cu:88-146,
implicit_schur_pcg_solver.cu:20-90); here they are verified in Pallas
interpret mode against plain numpy scatter/gather ground truth.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megba_tpu.ops.segtiles import (
    LANES,
    DevicePlan,
    TilePlan,
    build_tile_plan,
    coupling_expand,
    coupling_reduce,
    cross_perm,
    device_plan,
    expand_fallback,
    jtj_grad_reduce,
    make_sharded_dual_plans,
    reduce_fallback,
    tile_expand,
    tile_reduce,
)


def _check_plan_invariants(plan: TilePlan, idx: np.ndarray, num_segments):
    n_tiles = plan.n_tiles
    assert plan.n_slots == n_tiles * plan.tile
    # Every real edge appears exactly once.
    real = plan.mask > 0
    assert real.sum() == idx.shape[0]
    assert np.array_equal(np.sort(plan.perm[real]), np.arange(idx.shape[0]))
    # Slots carry the right segment ids.
    assert np.array_equal(plan.seg[real], idx[plan.perm[real]])
    # Each tile touches exactly one block, non-decreasing, all blocks
    # visited, first-flags correct.
    seg_by_tile = plan.seg.reshape(n_tiles, plan.tile)
    blk_by_tile = seg_by_tile // plan.block
    assert np.all(blk_by_tile == blk_by_tile[:, :1])
    tb = plan.tile_block
    assert np.array_equal(blk_by_tile[:, 0], tb)
    assert np.all(np.diff(tb) >= 0)
    assert set(tb.tolist()) == set(range(plan.num_blocks))
    first = np.ones_like(tb)
    first[1:] = tb[1:] != tb[:-1]
    assert np.array_equal(plan.tile_first, first)
    # local in range
    assert plan.local.min() >= 0 and plan.local.max() < plan.block
    _check_windows(plan.local[None, :], plan.tile_lo, plan.window,
                   plan.tile, plan.block)
    # Padding fill (running-max per block) keeps the whole slot stream
    # non-decreasing: the `indices_are_sorted=True` scatter promise.
    assert np.all(np.diff(plan.seg.astype(np.int64)) >= 0)


def _check_windows(local, tile_lo, window, tile, block):
    """Each tile's one-hot window: lane-aligned, inside the block, and
    holding every slot of the tile (padding too)."""
    assert window == block or (window % LANES == 0 and window < block)
    assert np.all(tile_lo % LANES == 0)
    assert np.all(tile_lo >= 0) and np.all(tile_lo + window <= block)
    rel = np.asarray(local).reshape(-1, tile) - np.asarray(tile_lo)[:, None]
    assert rel.min() >= 0 and rel.max() < window


@pytest.mark.parametrize("seed,n,ns,tile,block", [
    (0, 1000, 37, 64, 16),
    (1, 5000, 501, 128, 64),
    (2, 300, 900, 64, 128),   # more segments than edges (empty blocks)
    (3, 257, 1, 64, 8),       # single segment
    (4, 15000, 3000, 1024, 2048),  # ~5 edges a segment: windows engage
    (5, 4000, 1000, 256, 384),     # a block that is three lanes wide
])
def test_plan_invariants(seed, n, ns, tile, block):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, ns, n).astype(np.int32)
    plan = build_tile_plan(idx, ns, tile, block)
    _check_plan_invariants(plan, idx, ns)


@pytest.mark.parametrize("stream,window", [
    # Venice's points, 5 edges each at the production point geometry:
    # every tile spans 206 ids, which a 384-wide window holds.
    ("points", 384),
    # A camera-sorted stream at venice's degree (2,813 edges a camera)
    # needs its whole 128-wide camera block.
    ("cameras", 128),
])
def test_plan_window_width(stream, window):
    rng = np.random.default_rng(8)
    if stream == "points":
        ns, tile, block = 4000, 1024, 2048
        idx = rng.permutation(np.repeat(np.arange(ns), 5))
    else:
        ns, tile, block = 300, 2048, 128
        idx = np.repeat(np.arange(ns), 2813)
    plan = build_tile_plan(idx, ns, tile, block)
    _check_plan_invariants(plan, idx, ns)
    assert plan.window == window
    assert (plan.window < plan.block) == (stream == "points")


def test_plan_sorted_input_low_padding():
    # Pre-sorted camera-style input with many edges per segment: padding
    # stays under one tile per block.
    rng = np.random.default_rng(7)
    idx = np.sort(rng.integers(0, 16, 10_000)).astype(np.int32)
    plan = build_tile_plan(idx, 16, 128, 8)
    _check_plan_invariants(plan, idx, 16)
    assert plan.n_slots - plan.n_edges <= plan.num_blocks * 128


def _degree5(pts):
    """Each of `pts` observed 5 times (as venice's points are), shuffled."""
    return np.random.default_rng(1).permutation(np.repeat(pts, 5))


def _sharded_pt_plan() -> DevicePlan:
    """The point plan of the shard with fewer edges of a two-shard edge
    split: the planner pads it with junk-block tiles to the other's
    tile count."""
    pt = np.repeat(np.arange(1200), 5)
    cam = np.random.default_rng(6).integers(0, 16, pt.shape[0])
    _, _, _, dual = make_sharded_dual_plans(
        cam, pt, 16, 1200, 2, tile_cam=128, block_cam=128, tile_pt=128,
        block_pt=512, use_kernels=False)
    shards = [jax.tree_util.tree_map(lambda x: x[k], dual.pt)
              for k in range(2)]
    return min(shards, key=lambda dp: np.asarray(dp.mask).sum())


def _tiles_of(dp: DevicePlan):
    """Per-tile (smallest local id, largest, any real slot)."""
    local = np.asarray(dp.local).reshape(-1, dp.tile)
    real = np.asarray(dp.mask).reshape(-1, dp.tile) > 0
    return local.min(1), local.max(1), real.any(1)


# Edge streams for the kernel parity tests: (builder of the DevicePlan,
# segment count, what the stream must show).  The random ones are the
# streams these tests began with (whole-block one-hots); the others put
# the one-hot window through its cases at tile 128 and block 512, where
# a tile of 5-edge points spans about 26 ids.
CASES = {
    "random_t128_b8": (lambda: device_plan(build_tile_plan(
        np.random.default_rng(42).integers(0, 61, 3000), 61, 128, 8)),
        61, lambda dp: dp.window == dp.block),
    "random_t256_b128": (lambda: device_plan(build_tile_plan(
        np.random.default_rng(43).integers(0, 61, 3000), 61, 256, 128)),
        61, lambda dp: dp.window == dp.block),
    "random_t128_b32": (lambda: device_plan(build_tile_plan(
        np.random.default_rng(3).integers(0, 97, 2000), 97, 128, 32)),
        97, lambda dp: dp.window == dp.block),
    "random_t256_b32": (lambda: device_plan(build_tile_plan(
        np.random.default_rng(9).integers(0, 83, 3000), 83, 256, 32)),
        83, lambda dp: dp.window == dp.block),
    "degree5": (lambda: device_plan(build_tile_plan(
        _degree5(np.arange(1400)), 1400, 128, 512)),
        1400, lambda dp: dp.window == 128),
    "cross128": (lambda: device_plan(build_tile_plan(
        _degree5(np.arange(100, 300)), 300, 128, 512)),
        300, lambda dp: np.any(
            _tiles_of(dp)[0] // LANES != _tiles_of(dp)[1] // LANES)),
    "clamped": (lambda: device_plan(build_tile_plan(
        _degree5(np.arange(300, 512)), 512, 128, 512)),
        512, lambda dp: np.any(
            np.asarray(dp.tile_lo) < _tiles_of(dp)[0] // LANES * LANES)),
    "empty_block": (lambda: device_plan(build_tile_plan(
        _degree5(np.r_[0:300, 1024:1300]), 1300, 128, 512)),
        1300, lambda dp: not np.all(_tiles_of(dp)[2])),
    "many_tiles": (lambda: device_plan(build_tile_plan(
        _degree5(np.arange(300)), 300, 128, 512)),
        300, lambda dp: dp.local.shape[1] // dp.tile >= 10 * dp.num_blocks),
    "sharded_junk": (_sharded_pt_plan, 1200,
                     lambda dp: np.any(np.asarray(dp.tile_block)
                                       == -(-1200 // dp.block))),
}
WINDOW_CASES = [c for c in CASES if not c.startswith("random")]


def _case(name):
    """(DevicePlan, num_segments, per-slot segment ids, per-slot mask) of
    one stream, after checking that it shows what it is there for."""
    build, ns, shows = CASES[name]
    dp = build()
    assert (dp.window < dp.block) == (name in WINDOW_CASES)
    assert shows(dp), name
    _check_windows(dp.local, dp.tile_lo, dp.window, dp.tile, dp.block)
    seg = (np.asarray(dp.local)[0]
           + np.repeat(np.asarray(dp.tile_block), dp.tile) * dp.block)
    return dp, ns, seg, np.asarray(dp.mask)


def _scatter(rows, seg, mask, ns):
    """Numpy ground truth of a segment reduction over the real slots."""
    out = np.zeros((rows.shape[0], ns), np.float64)
    real = mask > 0
    for f in range(rows.shape[0]):
        np.add.at(out[f], seg[real], rows[f, real].astype(np.float64))
    return out


@pytest.mark.parametrize("F", [3, 12])
@pytest.mark.parametrize("case", ["random_t128_b8", "random_t256_b128",
                                  *WINDOW_CASES])
def test_tile_reduce_matches_numpy(F, case):
    dp, ns, seg, mask = _case(case)
    rng = np.random.default_rng(42)
    slot_data = (rng.standard_normal((F, seg.shape[0])) * mask).astype(
        np.float32)
    ref = _scatter(slot_data, seg, mask, ns)

    got = np.asarray(
        tile_reduce(jnp.asarray(slot_data), dp, interpret=True))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    fb = np.asarray(reduce_fallback(jnp.asarray(slot_data), dp))
    np.testing.assert_allclose(fb, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", ["random_t128_b32", *WINDOW_CASES])
def test_tile_expand_matches_take(case):
    dp, ns, seg, mask = _case(case)
    table = np.random.default_rng(3).standard_normal((9, ns)).astype(
        np.float32)

    got = np.asarray(tile_expand(jnp.asarray(table), dp, interpret=True))
    real = mask > 0
    expect = table[:, seg[real]]
    np.testing.assert_array_equal(got[:, real], expect)

    fb = np.asarray(expand_fallback(jnp.asarray(table), dp))
    np.testing.assert_array_equal(fb[:, real], expect)


def test_cross_perm_roundtrip():
    # Two plans over the same edges (camera-sorted and point-sorted
    # orders); cross_perm moves per-edge rows between slot orders.
    rng = np.random.default_rng(11)
    n = 4000
    cam = np.sort(rng.integers(0, 40, n)).astype(np.int32)
    pt = rng.integers(0, 700, n).astype(np.int32)
    plan_c = build_tile_plan(cam, 40, 128, 16)
    plan_p = build_tile_plan(pt, 700, 64, 128)

    x_edges = rng.standard_normal((2, n)).astype(np.float32)
    x_c = x_edges[:, plan_c.perm] * plan_c.mask
    x_p = x_edges[:, plan_p.perm] * plan_p.mask

    inv_c2p = cross_perm(plan_p, plan_c)  # for each pt-slot: cam slot
    moved = x_c[:, inv_c2p] * plan_p.mask
    np.testing.assert_array_equal(moved, x_p)

    inv_p2c = cross_perm(plan_c, plan_p)
    back = x_p[:, inv_p2c] * plan_c.mask
    np.testing.assert_array_equal(back, x_c)


@pytest.mark.parametrize("interp", [False, True])
@pytest.mark.parametrize("case", ["random_t256_b32", *WINDOW_CASES])
def test_coupling_expand_reduce(interp, case):
    # Fused (gather + J.x), (J^T.u + reduce) and (J^T J, -J^T r +
    # reduce) vs the composition of their unfused parts.
    dp, ns, seg, mask = _case(case)
    rng = np.random.default_rng(9)
    d, od = 9, 2
    nslots = seg.shape[0]
    J = rng.standard_normal((od * d, nslots)).astype(np.float32)
    J *= mask
    table = rng.standard_normal((d, ns)).astype(np.float32)
    u_in = rng.standard_normal((od, nslots)).astype(np.float32)
    r = (rng.standard_normal((od, nslots)) * mask).astype(np.float32)

    got_u = np.asarray(coupling_expand(
        jnp.asarray(table), jnp.asarray(J), dp, d,
        use_kernels=False, interpret=interp))
    pe = table[:, np.minimum(seg, ns - 1)]
    ref_u = np.stack([
        sum(J[o * d + a] * pe[a] for a in range(d)) for o in range(od)])
    np.testing.assert_allclose(got_u, ref_u, rtol=2e-5, atol=2e-5)

    got_r = np.asarray(coupling_reduce(
        jnp.asarray(J), jnp.asarray(u_in), dp, d,
        use_kernels=False, interpret=interp))
    te = np.stack([
        sum(J[o * d + b] * u_in[o] for o in range(od)) for b in range(d)])
    ref_r = _scatter(te, seg, mask, ns)
    np.testing.assert_allclose(got_r, ref_r, rtol=2e-4, atol=2e-4)

    h, g = jtj_grad_reduce(jnp.asarray(J), jnp.asarray(r), dp,
                           use_kernels=False, interpret=interp)
    h_f, g_f = jtj_grad_reduce(jnp.asarray(J), jnp.asarray(r), dp,
                               use_kernels=False)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_f),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_f),
                               rtol=2e-4, atol=2e-4)


def test_reduce_accumulation_many_tiles_per_block():
    # Forces the in-kernel accumulate branch (several tiles per block).
    rng = np.random.default_rng(5)
    n, ns = 4096, 4
    idx = rng.integers(0, ns, n).astype(np.int32)
    data = rng.standard_normal((5, n)).astype(np.float32)
    plan = build_tile_plan(idx, ns, 128, 8)
    assert plan.n_tiles > plan.num_blocks
    dp = device_plan(plan)
    slot_data = (data[:, plan.perm] * plan.mask).astype(np.float32)
    ref = np.zeros((5, ns), np.float64)
    for f in range(5):
        np.add.at(ref[f], idx, data[f].astype(np.float64))
    got = np.asarray(
        tile_reduce(jnp.asarray(slot_data), dp, interpret=True))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_kernel_check_interpret_mode_matches_fallbacks():
    """check_kernels (the chip's kernel gate) at both production
    geometries, run through the Pallas interpreter: all five kernels on
    both sides, the point side with and without a one-hot window, each
    within the bound."""
    from megba_tpu.ops.segtiles import KERNEL_CHECK_TOL, check_kernels

    results = check_kernels(interpret=True)
    names = {(name, geom.split()[0], geom.split()[3])
             for name, geom, _ in results}
    assert names == {
        (k, side, f"window={w}")
        for side, w in (("cam", 128), ("pt", 2048), ("pt", 384))
        for k in ("tile_reduce", "tile_expand", "jtj_grad_reduce",
                  "coupling_expand", "coupling_reduce")}
    assert all(err <= KERNEL_CHECK_TOL for _, _, err in results)


def test_kernel_mismatch_raises_and_cpu_probe_is_off():
    """A disagreement (NaN included) raises instead of degrading, naming
    every result, and off the TPU the use_kernels default is the XLA
    path."""
    from megba_tpu.ops.segtiles import (
        KernelMismatch,
        probe_kernels,
        require_all_close,
    )

    ok = ("tile_expand", "cam", 0.0)
    require_all_close([ok, ("tile_reduce", "pt", 1e-6)])
    with pytest.raises(KernelMismatch, match="(?s)1 kernel.*tile_reduce"):
        require_all_close([ok, ("tile_reduce", "pt", 0.5)])
    with pytest.raises(KernelMismatch):
        require_all_close([("nan", "cam", float("nan"))])
    assert probe_kernels() is False
