"""The edge-sharded solve (`flat_solve(world_size=4)`) against the plain
reference, its collectives under the `megba.collective` scope, and the
per-shard counters and host phases a sharded solve reports.  CPU, four of
the conftest's virtual devices."""

import functools
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

from megba_tpu.common import (
    AlgoOption,
    JacobianMode,
    ProblemOption,
    SolverOption,
)
from megba_tpu.ops.residuals import make_residual_jacobian_fn
from megba_tpu.solve import flat_solve
from megba_tpu.utils.timing import PhaseTimer

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import attribution  # noqa: E402
import scene  # noqa: E402
import tracereduce  # noqa: E402
from reference import Recipe, Reference  # noqa: E402

RECIPE = dict(initial_region=1e4, epsilon1=1.0, epsilon2=1e-10)
# An instruction of a collective kind; a tuple's shape holds spaces.
_COLLECTIVE = re.compile(r"\s(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)(-start)?\(")


def _scene(dtype, cams=16, pts=800, edges=4000):
    return scene.make_scene(cams, pts, edges, seed=5, graph_seed=1,
                            dtype=dtype)


def _option(dtype, world_size, max_iter=3, **kw):
    return ProblemOption(
        dtype=dtype, world_size=world_size,
        jacobian_mode=JacobianMode.ANALYTICAL,
        algo_option=AlgoOption(max_iter=max_iter, **RECIPE),
        solver_option=SolverOption(max_iter=100, tol=0.1, refuse_ratio=1.0),
        **kw)


def _solve(s, option, use_tiled, **kw):
    return flat_solve(make_residual_jacobian_fn(mode=JacobianMode.ANALYTICAL),
                      s.cameras0, s.points0, s.obs, s.cam_idx, s.pt_idx,
                      option, use_tiled=use_tiled, **kw)


@pytest.mark.parametrize("use_tiled,dtype,rtol", [
    # The XLA lowering in float64: the psums of four shards reorder sums
    # only, so the reference's tolerance for one device holds.
    (False, np.float64, 1e-10),
    # The tiled lowering runs in float32 only (its kernels accumulate in
    # float32; the build refuses float64 plans), so the trial costs agree
    # to float32 rounding of a 4,000-edge sum: 4e-7 measured.
    (True, np.float32, 1e-5),
], ids=["xla-f64", "tiled-f32"])
def test_sharded_solve_follows_the_reference(use_tiled, dtype, rtol):
    s = _scene(dtype)
    res = _solve(s, _option(dtype, 4), use_tiled)
    ref = Reference(s.obs, s.cam_idx, s.pt_idx, np.float64)
    out = ref.lm_trial_costs(s.cameras0, s.points0,
                             Recipe(1e4, 1.0, 1e-10, 0.1, 1.0, 100), 3)
    it = int(res.iterations)
    assert it == 3
    np.testing.assert_allclose(np.asarray(res.trace.cost)[:it],
                               out["cost"][:it], rtol=rtol)
    np.testing.assert_array_equal(np.asarray(res.trace.pcg_iters)[:it],
                                  out["pcg_iters"][:it])
    assert ref.cost(s.cameras0, s.points0) == pytest.approx(
        float(res.initial_cost), rel=rtol)


@functools.lru_cache(maxsize=None)
def _compiled(world_size):
    s = _scene(np.float32)
    return _solve(s, _option(np.float32, world_size), True,
                  lower_only=True).compile().as_text()


def _collectives(world_size):
    return [line for line in _compiled(world_size).splitlines()
            if _COLLECTIVE.search(line)]


def test_every_collective_of_the_sharded_program_is_named():
    found = _collectives(4)
    # The LM cost sums, the Schur build, the reduced right-hand side,
    # the two of each S.p, the back-substitution and the predicted cost.
    assert len(found) >= 5, found
    for line in found:
        op_name = re.search(r'op_name="([^"]*)"', line)
        assert op_name and "/megba.collective/" in op_name.group(1), line
    # Inside the layer scopes, which keep their time.
    paths = [re.search(r'op_name="([^"]*)"', x).group(1) for x in found]
    assert any("megba.pcg_core/" in p for p in paths)
    assert any("megba.schur_build/megba.collective" in p for p in paths)


@pytest.mark.parametrize("world_size", [2, 4])
def test_the_trace_tools_attribute_the_collectives(world_size):
    """attribution.py gives every collective the `megba.collective` layer;
    tracereduce, whose layers the per-layer metrics read, still gives each
    one the layer it had before the scope (the PCG loop's stay in
    `megba.pcg_core`)."""
    paths = tracereduce.scopes_from_hlo(_compiled(world_size))
    names = [re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", x).group(1)
             for x in _collectives(world_size)]
    assert names
    for name in names:
        path = paths[name]
        assert attribution.layer_of(path) == "megba.collective", path
        assert tracereduce.innermost_scope(path) == \
            tracereduce.innermost_scope(
                path.replace("/megba.collective", "")), path
    assert "megba.pcg_core" in {tracereduce.innermost_scope(paths[n])
                                for n in names}


def test_the_one_device_program_has_no_collective():
    hlo = _compiled(1)
    assert not _COLLECTIVE.search(hlo)
    assert "megba.collective" not in hlo


def test_a_sharded_solve_reports_its_shards_and_host_phases(tmp_path):
    s = _scene(np.float32, cams=24, pts=3000, edges=15000)
    out = tmp_path / "t.jsonl"
    timer = PhaseTimer()
    jax.block_until_ready(_solve(
        s, _option(np.float32, 4, max_iter=2, telemetry=str(out)), True,
        timer=timer))
    (line,) = out.read_text().splitlines()
    tiles = json.loads(line)["tiles"]
    assert tiles["plan"] == "tiled_sharded"
    assert len(tiles["shard_edges"]) == 4
    assert sum(tiles["shard_edges"]) == s.obs.shape[0]
    slots = tiles["shard_slots"]
    assert slots >= max(tiles["shard_edges"])
    assert tiles["shard_occupancy"] == [round(e / slots, 4)
                                        for e in tiles["shard_edges"]]
    assert tiles["window_pt"] % 128 == 0
    assert 0 < tiles["window_share_pt"] <= 1
    assert tiles["window_cam"] > 0
    d = timer.as_dict()
    for child in ("plan.key", "plan.gather", "dispatch.call"):
        parent = child.split(".")[0]
        assert d[child]["calls"] == 1, child
        assert d[child]["total_s"] <= d[parent]["total_s"], child
    # The jitted call transfers the operands; nothing is placed before it.
    assert "dispatch.upload" not in d
