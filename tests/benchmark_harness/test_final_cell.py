"""Tests of the benchmark's four-chip cell `final.recipe.4chip`: its
configuration, its rehearsal on four virtual CPU devices through the
sharded tiled lowering, and its limits."""

from __future__ import annotations

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

CELL = "final.recipe.4chip"
REHEARSE = "0.002"  # 27 cameras, 8,912 points, 57,973 observations


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_holds_all_of_final_over_four_chips():
    cell = harness.load_cell(CELL)
    c = cell["config"]
    assert (c["num_cameras"], c["num_points"], c["num_observations"]) == (
        13682, 4456117, 28987644)
    assert c["residual_components"] == 2 * c["num_observations"]
    assert c["world_size"] == cell["cell"]["chips"] == 4
    (entry,) = [x for x in _bench()["configs"] if x["name"] == "final"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"])


def test_the_rehearsal_is_correct_through_the_sharded_plans(
        monkeypatch, tmp_path):
    import run

    report = tmp_path / "t.jsonl"
    monkeypatch.setenv("MEGBA_TELEMETRY", str(report))
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", "2147483659",
                       "--seconds", "0.3", "--trace", "0",
                       "--rehearse", REHEARSE])
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]
    assert result["device"]["count"] == 4
    tiles = [json.loads(x)["tiles"] for x in
             report.read_text().splitlines()]
    assert tiles and all(t["plan"] == "tiled_sharded" for t in tiles)
    assert sum(tiles[0]["shard_edges"]) == 57973


@pytest.mark.parametrize("number", ["init_cost_rel", "lm_cost_rel",
                                    "final_cost_rel"])
def test_each_limit_lies_between_its_readings(number):
    lim = harness.load_cell(CELL)["limits"]
    r = lim["readings"]
    program = max(r["program_full_size_chip"][number],
                  r["program_max_cpu_reduced_size"][number])
    assert (program < lim["limits"][number]
            < r["control_min_cpu_reduced_size"][number])

