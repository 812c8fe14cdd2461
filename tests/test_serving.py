"""Serving-layer tests: shape buckets, batched solves, queue, pool.

Compile discipline: tier-1 runs at ~80% of its time budget, so every
test here draws from ONE canonical option per dtype (OPT64 / OPT32) and
a small closed set of (bucket, lanes) shapes — the jit caches and the
persistent compile cache make the marginal cost of each extra test a
solve, not a compile.  Every test that traces/compiles a solver program
is additionally marked `slow`, but one: the tier-1 sweep (`pytest -m
'not slow'`) keeps the host-side property/unit tests plus
`test_fleet_vs_natural_flat_solve_rtol` (batched lanes against
flat_solve), and the full two-process lane (scripts/run_tests.sh, no
filter) runs everything.
"""

import dataclasses
import json
import time

import numpy as np
import pytest

from megba_tpu.common import (
    AlgoOption,
    ProblemOption,
    SolverOption,
    SolveStatus,
)
from megba_tpu.io.synthetic import make_fleet, make_synthetic_bal
from megba_tpu.ops.residuals import make_residual_jacobian_fn
from megba_tpu.serving import (
    BucketLadder,
    CompilePool,
    FleetProblem,
    FleetQueue,
    FleetStats,
    classify,
    pad_to_class,
    solve_many,
)
from megba_tpu.solve import flat_solve

TERMINAL = {int(s) for s in SolveStatus}

OPT64 = ProblemOption(dtype=np.float64,
                      algo_option=AlgoOption(max_iter=6),
                      solver_option=SolverOption(max_iter=12, tol=1e-10))
OPT32 = dataclasses.replace(OPT64, dtype=np.float32)


def _mk(seed, n_pt, n_cam=4, dtype=np.float64):
    s = make_synthetic_bal(num_cameras=n_cam, num_points=n_pt,
                           obs_per_point=3, seed=seed, param_noise=2e-2,
                           pixel_noise=0.3, dtype=dtype)
    return FleetProblem.from_synthetic(s, name=f"s{seed}_p{n_pt}")


def _bits(a) -> bytes:
    return np.asarray(a).tobytes()


# ---------------------------------------------------------------------------
# Bucket ladder properties
# ---------------------------------------------------------------------------

def test_ladder_monotone_and_covering():
    ladder = BucketLadder()
    r = np.random.default_rng(0)
    ns = np.concatenate([np.arange(1, 70),
                         r.integers(1, 3_000_000, size=300)])
    for bucket in (ladder.bucket_cams, ladder.bucket_points,
                   ladder.bucket_edges, ladder.bucket_lanes):
        got = [bucket(int(n)) for n in sorted(ns)]
        # covering: a problem always fits its bucket
        assert all(b >= n for b, n in zip(got, sorted(ns)))
        # monotone: more of anything never lands in a smaller bucket
        assert all(b2 >= b1 for b1, b2 in zip(got, got[1:]))
        # idempotent: a bucket is its own bucket (ladder is a closure)
        assert all(bucket(b) == b for b in got)


def test_ladder_is_powers_of_two_over_floor():
    ladder = BucketLadder(cam_floor=4, pt_floor=16)
    for n in range(1, 200):
        b = ladder.bucket_cams(n)
        assert b % 4 == 0 and (b // 4) & (b // 4 - 1) == 0
    # edge buckets stay EDGE_QUANTUM multiples (solver invariant)
    from megba_tpu.core.fm import EDGE_QUANTUM

    for n in (1, 100, 2048, 2049, 5000, 100_000):
        assert ladder.bucket_edges(n) % EDGE_QUANTUM == 0


def test_ladder_validation():
    with pytest.raises(ValueError):
        BucketLadder(cam_floor=0)
    with pytest.raises(ValueError):
        BucketLadder(edge_floor=1000)  # not an EDGE_QUANTUM multiple
    with pytest.raises(ValueError):
        classify(0, 10, 10, np.float64, BucketLadder())


def test_pad_to_class_invariants():
    p = _mk(5, 37, n_cam=5)
    sc = classify(*p.dims(), np.float64, BucketLadder())
    pp = pad_to_class(p.cameras, p.points, p.obs, p.cam_idx, p.pt_idx, sc)
    assert pp.cameras.shape[0] == sc.n_cam
    assert pp.points.shape[0] == sc.n_pt
    assert pp.obs.shape[0] == sc.n_edge
    # padded edges masked out, indices in range, cam stream sorted
    n = pp.n_edge
    assert pp.mask[:n].all() and not pp.mask[n:].any()
    assert pp.cam_idx.max() < pp.n_cam and pp.pt_idx.max() < pp.n_pt
    assert np.all(np.diff(pp.cam_idx) >= 0)
    # pad region flagged fixed, real region not
    assert not pp.cam_fixed[:pp.n_cam].any() and pp.cam_fixed[pp.n_cam:].all()
    assert not pp.pt_fixed[:pp.n_pt].any() and pp.pt_fixed[pp.n_pt:].all()
    # a problem too big for the class is rejected
    small = dataclasses.replace(sc, n_cam=2)
    with pytest.raises(ValueError):
        pad_to_class(p.cameras, p.points, p.obs, p.cam_idx, p.pt_idx, small)


# ---------------------------------------------------------------------------
# Padding exactness + lane invariance (the fleet numerics contract)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_padded_solve_bitwise_equals_unpadded_f64():
    """Bucket padding is an exact no-op: the same problem solved at its
    minimal shape class and at a strictly larger one (more cameras,
    points AND edges) produces bitwise-identical parameters, cost and
    iteration count."""
    p = _mk(3, 32)
    base = solve_many([p], OPT64)[0]
    big = solve_many([p], OPT64, ladder=BucketLadder(
        cam_floor=8, pt_floor=64, edge_floor=4096))[0]
    assert big.shape != base.shape
    assert _bits(base.cameras) == _bits(big.cameras)
    assert _bits(base.points) == _bits(big.points)
    assert _bits(base.cost) == _bits(big.cost)
    assert base.iterations == big.iterations
    assert base.status == big.status


@pytest.mark.slow
def test_padded_solve_edge_axis_bitwise_f32():
    """f32: zero-padding the EDGE axis to a bigger power-of-two bucket
    keeps the whole parameter trajectory bitwise (the compensated-sum
    fold absorbs appended zero rows exactly); the carried cost scalar
    may differ in its last ulps (the [od, nE] ravel interleaves the two
    observation rows), so it gets an ulp-tight allclose instead."""
    p = _mk(3, 32, dtype=np.float32)
    base = solve_many([p], OPT32)[0]
    big = solve_many([p], OPT32,
                     ladder=BucketLadder(edge_floor=4096))[0]
    assert big.shape.n_edge == 2 * base.shape.n_edge
    assert _bits(base.cameras) == _bits(big.cameras)
    assert _bits(base.points) == _bits(big.points)
    assert base.iterations == big.iterations
    assert base.status == big.status
    np.testing.assert_allclose(big.cost, base.cost, rtol=1e-5)


@pytest.mark.slow
def test_padded_solve_campt_equivalence_f32():
    """f32 camera/point padding reorders the compensated reductions
    (interleaved zeros in the feature-major ravel), so exact bitwise is
    out of reach — but the solve must land on the same answer within
    the acceptance band (rtol 1e-6 on cost) and terminate."""
    p = _mk(3, 32, dtype=np.float32)
    base = solve_many([p], OPT32)[0]
    big = solve_many([p], OPT32, ladder=BucketLadder(
        cam_floor=16, pt_floor=64))[0]
    assert big.status in TERMINAL and base.status in TERMINAL
    np.testing.assert_allclose(big.cost, base.cost, rtol=1e-6)
    # Parameters sit in the f32 convergence basin: weakly-constrained
    # directions (the k1/k2 distortion terms) wander ~1e-4 relative at
    # identical cost, so the parameter band is looser than the cost's.
    np.testing.assert_allclose(big.cameras, base.cameras,
                               rtol=1e-3, atol=1e-4)


@pytest.mark.slow
@pytest.mark.parametrize("dtype,opt", [(np.float64, OPT64),
                                       (np.float32, OPT32)],
                         ids=["f64", "f32"])
def test_lane_placement_invariance_bitwise(dtype, opt):
    """The fleet isolation contract: at a fixed (bucket, lane count),
    a problem's result is bitwise independent of its lane position and
    of WHO its batch-mates are — and reruns are deterministic."""
    p, q, r = (_mk(3, 32, dtype=dtype), _mk(7, 29, dtype=dtype),
               _mk(11, 31, dtype=dtype))
    a = solve_many([p, q], opt)
    assert a[0].shape == a[1].shape  # same bucket (29/31 pts pad to 32)
    b = solve_many([q, p], opt)  # p moves to lane 1
    c = solve_many([p, r], opt)  # different batch-mate
    d = solve_many([p, q], opt)  # rerun
    for other in (b[1], c[0], d[0]):
        assert _bits(a[0].cameras) == _bits(other.cameras)
        assert _bits(a[0].points) == _bits(other.points)
        assert _bits(a[0].cost) == _bits(other.cost)


# ---------------------------------------------------------------------------
# The acceptance fleet: 16 heterogeneous problems vs flat_solve
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_16_matches_flat_solve_one_compile_per_bucket():
    """solve_many over a 16-problem heterogeneous fleet returns
    per-problem params/cost/SolveStatus matching individual flat_solve
    runs, with the retrace sentinel certifying <= 1 batched-program
    compile per shape bucket (and zero on a rerun)."""
    from megba_tpu.analysis import retrace

    fleet = make_fleet(16, size_range=(12, 96), seed=0)
    probs = [FleetProblem.from_synthetic(s, name=f"fleet{i}")
             for i, s in enumerate(fleet)]
    ladder = BucketLadder()
    stats = FleetStats()

    base = retrace.snapshot()
    results = solve_many(probs, OPT64, ladder=ladder, stats=stats)
    new = {k: v for k, v in retrace.snapshot().items()
           if k[0] == "serving.batched"
           and v > base.get(k, 0)}
    buckets = {(r.shape, r.lanes) for r in results}
    # one compile per (bucket, lane-count), ever — and never a
    # duplicate signature (that would be a jit cache bust)
    assert all(v - base.get(k, 0) <= 1 for k, v in new.items()), new
    assert len(new) <= len(buckets), (new, buckets)

    # a rerun of the same fleet compiles NOTHING new
    base2 = retrace.snapshot()
    again = solve_many(probs, OPT64, ladder=ladder)
    assert not {k: v for k, v in retrace.snapshot().items()
                if k[0] == "serving.batched" and v > base2.get(k, 0)}

    f = make_residual_jacobian_fn()
    for p, res, res2 in zip(probs, results, again):
        # determinism across calls
        assert _bits(res.cameras) == _bits(res2.cameras)
        assert _bits(res.cost) == _bits(res2.cost)
        assert res.status in TERMINAL
        # individual reference run AT the same shape class (flat_solve
        # on the padded arrays + fixed masks + the bucket's edge mask —
        # identical static shapes AND identical masked-edge no-ops, so
        # the only difference is batching itself)
        pp = pad_to_class(p.cameras, p.points, p.obs, p.cam_idx,
                          p.pt_idx, res.shape)
        ref = flat_solve(f, pp.cameras, pp.points, pp.obs, pp.cam_idx,
                         pp.pt_idx, OPT64, edge_mask=pp.mask,
                         cam_fixed=pp.cam_fixed, pt_fixed=pp.pt_fixed,
                         use_tiled=False)
        assert int(ref.status) == res.status, p.name
        np.testing.assert_allclose(res.cost, np.asarray(ref.cost),
                                   rtol=1e-6, err_msg=p.name)
        np.testing.assert_allclose(
            res.cameras, np.asarray(ref.cameras)[:pp.n_cam],
            rtol=1e-6, atol=1e-8, err_msg=p.name)
        np.testing.assert_allclose(
            res.points, np.asarray(ref.points)[:pp.n_pt],
            rtol=1e-6, atol=1e-8, err_msg=p.name)
        # padded camera/point lanes never moved off their zero padding
        assert not np.any(np.asarray(ref.cameras)[pp.n_cam:])

    # stats coherence for the run
    d = stats.as_dict()
    assert d["problems"] == 16
    assert d["batches"] == len(buckets)
    assert 0.0 < d["padding_waste"] < 1.0
    assert d["problems_per_sec"] > 0.0


def test_fleet_vs_natural_flat_solve_rtol():
    """Cross-shape check: lanes also match flat_solve at the problem's
    NATURAL (unbucketed) shapes within the acceptance band."""
    probs = [_mk(3, 32), _mk(5, 37, n_cam=5), _mk(9, 20, n_cam=3)]
    results = solve_many(probs, OPT64)
    f = make_residual_jacobian_fn()
    for p, res in zip(probs, results):
        ref = flat_solve(f, p.cameras, p.points, p.obs, p.cam_idx,
                         p.pt_idx, OPT64, use_tiled=False)
        assert int(ref.status) == res.status
        assert int(ref.iterations) == res.iterations
        np.testing.assert_allclose(res.cost, np.asarray(ref.cost),
                                   rtol=1e-6)
        np.testing.assert_allclose(res.cameras, np.asarray(ref.cameras),
                                   rtol=1e-6, atol=1e-8)


def test_make_fleet_deterministic_and_prefix_stable():
    a = make_fleet(8, size_range=(12, 96), seed=0)
    b = make_fleet(8, size_range=(12, 96), seed=0)
    for x, y in zip(a, b):
        assert _bits(x.cameras0) == _bits(y.cameras0)
        assert _bits(x.obs) == _bits(y.obs)
    # growing the fleet never reshuffles existing members
    c = make_fleet(4, size_range=(12, 96), seed=0)
    for x, y in zip(c, a):
        assert _bits(x.obs) == _bits(y.obs)
    # a different seed is a different fleet
    d = make_fleet(4, size_range=(12, 96), seed=1)
    assert any(_bits(x.obs) != _bits(y.obs) for x, y in zip(d, a))
    # heterogeneous sizes
    assert len({s.points_gt.shape[0] for s in a}) > 1
    with pytest.raises(ValueError):
        make_fleet(0)


# ---------------------------------------------------------------------------
# Compile pool + warmup manifests
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_compile_pool_warm_manifest_roundtrip(tmp_path):
    """Warming from a manifest AOT-compiles the bucket; a dispatch that
    follows runs the precompiled executable WITHOUT tracing anything
    new (the sentinel proves first-request latency is dispatch-only)."""
    from megba_tpu.analysis import retrace

    engine = make_residual_jacobian_fn()
    p = _mk(21, 16, n_cam=3)
    ladder = BucketLadder()
    sc = classify(*p.dims(), OPT64.dtype, ladder)

    # A config no other test dispatches, so the warmed program is
    # guaranteed fresh regardless of test ordering.
    opt = dataclasses.replace(OPT64, algo_option=AlgoOption(max_iter=4))
    stats = FleetStats()
    pool = CompilePool(stats=stats)
    entry = {"shape": sc.to_dict(), "lanes": 1, "cd": 9, "pd": 3, "od": 2}
    assert pool.warm(engine, opt, [entry]) == 1
    assert pool.warm(engine, opt, [entry]) == 0  # idempotent

    manifest = tmp_path / "warmup.json"
    pool.save_manifest(str(manifest), option=opt)
    doc = json.loads(manifest.read_text())
    assert doc["schema"].startswith("megba_tpu.fleet_manifest")
    assert doc["entries"] == [entry]

    # a fresh pool warming the same manifest finds everything built
    pool2 = CompilePool()
    assert pool2.warm_from_manifest(str(manifest), engine, opt) == 0

    # dispatch through the warmed pool: zero new traces of any site
    base = retrace.snapshot()
    res = solve_many([p], opt, ladder=ladder, pool=pool, stats=stats)[0]
    new = {k: v for k, v in retrace.snapshot().items()
           if v > base.get(k, 0)}
    assert not new, f"warmed dispatch traced: {new}"
    assert res.status in TERMINAL
    assert stats.pool_hits >= 1

    # a manifest recorded under a different option fingerprint warns
    # (checked against an EMPTY manifest so the test stays compile-free)
    empty = tmp_path / "empty.json"
    CompilePool().save_manifest(str(empty), option=OPT64)
    other = dataclasses.replace(
        OPT64, algo_option=AlgoOption(max_iter=5))
    with pytest.warns(UserWarning, match="different option"):
        assert CompilePool().warm_from_manifest(
            str(empty), engine, other) == 0

    with pytest.raises(ValueError, match="not a fleet warmup manifest"):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"schema": "nope"}))
        CompilePool().warm_from_manifest(str(bad), engine, OPT64)


# ---------------------------------------------------------------------------
# Async dispatch queue
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_queue_max_batch_flush_matches_solve_many():
    """8 same-bucket problems through a max_batch=4 queue flush as two
    4-lane batches whose results are bitwise what solve_many produces
    for the same 4-problem batches (lane invariance at fixed B)."""
    probs = [_mk(100 + i, 29 + (i % 4)) for i in range(8)]  # one bucket
    with FleetQueue(OPT64, max_batch=4, max_wait_s=30.0) as q:
        futures = [q.submit(p) for p in probs]
        got = [f.result(timeout=600) for f in futures]
    assert all(g.lanes == 4 for g in got)
    ref = solve_many(probs[:4], OPT64) + solve_many(probs[4:], OPT64)
    for g, r in zip(got, ref):
        assert _bits(g.cameras) == _bits(r.cameras)
        assert _bits(g.cost) == _bits(r.cost)
        assert g.status in TERMINAL
        assert g.latency_s > 0.0


@pytest.mark.slow
def test_queue_deadline_flush():
    """A lone problem must not wait forever for batch-mates: the
    max_wait deadline flushes it (lanes == 1)."""
    p = _mk(3, 32)
    with FleetQueue(OPT64, max_batch=64, max_wait_s=0.05) as q:
        t0 = time.monotonic()
        fut = q.submit(p)
        res = fut.result(timeout=600)
        assert res.lanes == 1
        assert time.monotonic() - t0 >= 0.05
    assert res.status in TERMINAL


@pytest.mark.slow
def test_queue_flush_and_close_drain():
    p, p2 = _mk(3, 32), _mk(7, 29)
    q = FleetQueue(OPT64, max_batch=64, max_wait_s=600.0)
    try:
        f1 = q.submit(p)
        q.flush()  # ignores the 10-minute deadline
        assert f1.result(timeout=600).status in TERMINAL
        f2 = q.submit(p2)
    finally:
        q.close()  # drains f2
    assert f2.result(timeout=600).status in TERMINAL
    with pytest.raises(RuntimeError, match="closed"):
        q.submit(p)


@pytest.mark.slow
def test_queue_failed_batch_propagates_and_keeps_serving():
    """A batch that dies (here: a malformed problem that cannot trace)
    rejects ITS futures with the real error; the queue keeps serving
    later submissions."""
    bad = _mk(3, 32)
    bad = dataclasses.replace(bad, cameras=bad.cameras[:, :2])  # cd=2
    good = _mk(3, 32)
    with FleetQueue(OPT64, max_batch=1, max_wait_s=10.0) as q:
        fb = q.submit(bad)
        with pytest.raises(Exception):
            fb.result(timeout=600)
        fg = q.submit(good)
        assert fg.result(timeout=600).status in TERMINAL


def test_queue_validation():
    with pytest.raises(ValueError):
        FleetQueue(OPT64, max_batch=0)
    with pytest.raises(ValueError):
        FleetQueue(OPT64, max_wait_s=-1.0)
    with pytest.raises(ValueError, match="world_size"):
        solve_many([_mk(3, 32)],
                   dataclasses.replace(OPT64, world_size=2))


# ---------------------------------------------------------------------------
# Stats + plan cache + telemetry/CLI satellites
# ---------------------------------------------------------------------------

def test_fleet_stats_metrics():
    s = FleetStats()
    s.record_batch("b1", lanes=4, n_real=3, edges_real=300,
                   edge_bucket=2048, wall_s=0.5)
    s.record_batch("b2", lanes=1, n_real=1, edges_real=2048,
                   edge_bucket=2048, wall_s=0.5)
    s.record_pool(True)
    s.record_pool(False)
    d = s.as_dict()
    assert d["problems"] == 4 and d["batches"] == 2
    assert d["problems_per_sec"] == pytest.approx(4.0)
    waste = 1.0 - (300 + 2048) / (5 * 2048)
    assert d["padding_waste"] == pytest.approx(waste)
    assert d["bucket_occupancy"]["b1"] == pytest.approx(0.75)
    assert d["pool_hit_rate"] == pytest.approx(0.5)
    assert "problems/s" in s.report()


def test_plan_cache_capacity_env_and_evictions(monkeypatch):
    """MEGBA_PLAN_CACHE resizes the DualPlans LRU; evictions count."""
    from megba_tpu.ops import segtiles

    def graph(seed):
        r = np.random.default_rng(seed)
        cam = np.sort(r.integers(0, 4, size=32)).astype(np.int32)
        pt = r.integers(0, 16, size=32).astype(np.int32)
        return cam, pt

    monkeypatch.setenv("MEGBA_PLAN_CACHE", "2")
    segtiles._PLAN_CACHE.clear()
    base_ev = segtiles.plan_cache_evictions()
    for seed in range(4):  # 4 distinct graphs through a capacity-2 LRU
        cam, pt = graph(seed)
        _, hit = segtiles.cached_dual_plans(cam, pt, 4, 16,
                                            use_kernels=False)
        assert not hit
    assert len(segtiles._PLAN_CACHE) == 2
    assert segtiles.plan_cache_evictions() - base_ev == 2
    # LRU order: the two newest graphs are hits, the oldest was evicted
    cam, pt = graph(3)
    _, hit = segtiles.cached_dual_plans(cam, pt, 4, 16, use_kernels=False)
    assert hit
    cam, pt = graph(0)
    _, hit = segtiles.cached_dual_plans(cam, pt, 4, 16, use_kernels=False)
    assert not hit

    monkeypatch.setenv("MEGBA_PLAN_CACHE", "zero")
    with pytest.raises(ValueError, match="MEGBA_PLAN_CACHE"):
        segtiles.plan_cache_capacity()
    monkeypatch.setenv("MEGBA_PLAN_CACHE", "0")
    with pytest.raises(ValueError, match="MEGBA_PLAN_CACHE"):
        segtiles.plan_cache_capacity()
    monkeypatch.delenv("MEGBA_PLAN_CACHE")
    assert segtiles.plan_cache_capacity() == 8


@pytest.mark.slow
def test_solve_many_telemetry_reports_and_aggregate_cli(tmp_path,
                                                       monkeypatch):
    """Each fleet problem emits one SolveReport with a `fleet` block;
    the summarize --aggregate CLI renders status counts, throughput and
    latency percentiles from the stream."""
    sink = tmp_path / "fleet.jsonl"
    probs = [_mk(3, 32), _mk(7, 29)]
    opt = dataclasses.replace(OPT64, telemetry=str(sink))
    solve_many(probs, opt)

    from megba_tpu.observability.report import SolveReport

    lines = [l for l in sink.read_text().splitlines() if l.strip()]
    assert len(lines) == 2
    reps = [SolveReport.from_json(l) for l in lines]
    for rep in reps:
        assert rep.fleet["bucket"] == "c4_p32_e2048_float64"
        assert rep.fleet["lanes"] == 2
        assert rep.fleet["latency_s"] > 0
        assert rep.result["status_name"] in {"converged", "max_iter"}
        assert rep.fleet["stats"]["problems"] >= 2
    assert {rep.fleet["lane"] for rep in reps} == {0, 1}

    from megba_tpu.observability import summarize

    out = summarize.aggregate_paths([str(sink)])
    assert "fleet aggregate: 2 solves" in out
    assert "p50" in out and "p95" in out
    assert "bucket c4_p32_e2048_float64: 2 solves" in out

    # the CLI flag wires through main()
    rc = summarize.main(["--aggregate", str(sink)])
    assert rc == 0


def test_aggregate_reports_without_fleet_context():
    """--aggregate degrades gracefully on plain (non-fleet) report
    streams: latency falls back to the summed phase clock."""
    from megba_tpu.observability.report import SolveReport
    from megba_tpu.observability.summarize import aggregate_reports

    reps = [
        SolveReport(problem={}, config={}, backend={},
                    phases={"dispatch": {"total_s": 0.25, "calls": 1}},
                    result={"status_name": "converged"},
                    created_unix=100.0 + i)
        for i in range(3)
    ]
    out = aggregate_reports(reps)
    assert "3 solves" in out and "status converged: 3" in out
    assert "p50 250.0 ms" in out
    assert aggregate_reports([]) == "no reports"
