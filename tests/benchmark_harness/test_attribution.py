"""Tests of benchmark/attribution.py, the reduction of a traced solve by
every name the program gives its time (host spans, `megba.*` layers,
Pallas kernels, idle time by span), and of the pcg_setup_ms_per_lm
reader.  All on the CPU."""

from __future__ import annotations

import glob
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

import attribution  # noqa: E402
import harness  # noqa: E402
import tracereduce  # noqa: E402

Op, Span = tracereduce.Op, tracereduce.Span
# The op-name path of a coupling kernel in the PCG loop, as the chip's
# compiled program gives it.
TPU_PATH = ("jit(fn)/megba.lm_solve/while/body/megba.pcg/megba.pcg_core/"
            "while/body/jit(_expand_matvec_call)/"
            "coupling_expand_cam_to_edge/pallas_call")


def test_layer_and_kernel_come_from_the_op_name_path():
    assert attribution.layer_of(TPU_PATH) == "megba.pcg_core"
    assert attribution.kernel_of(TPU_PATH) == "coupling_expand_cam_to_edge"
    layer_of, kernel_of = attribution.layer_of, attribution.kernel_of
    assert layer_of("jit(fn)/megba.lm_solve/while/body/"
                    "megba.lm_step/mul") == "megba.lm_step"
    assert layer_of("jit(fn)/megba.pcg_coreX/y") == "megba.pcg_coreX"
    assert layer_of("jit(fn)/add") is None
    assert layer_of(None) is None
    assert kernel_of("jit(fn)/megba.pcg/fusion") is None


def test_span_seconds_clip_to_the_window():
    spans = [Span(0, 1000, tracereduce.WINDOW_SPAN),
             Span(-50, 100, "megba.flat_solve"),  # half outside
             Span(10, 20, "megba.phase.plan"),
             Span(40, 20, "megba.phase.plan"),
             Span(990, 100, "bench.wait"),
             Span(100, 50, "PjitFunction")]  # not a program span
    secs, calls = attribution.span_seconds(spans, 0, 1000)
    assert secs == pytest.approx({"megba.flat_solve": 50e-9,
                                  "megba.phase.plan": 40e-9,
                                  "bench.wait": 10e-9})
    assert calls == {"megba.flat_solve": 1, "megba.phase.plan": 2,
                     "bench.wait": 1}


def test_op_seconds_by_layer_and_kernel():
    paths = {"k.1": TPU_PATH, "f.2": "jit(fn)/megba.lm_solve/megba.lm_step/x",
             "copy.3": None, "while.4": "jit(fn)/megba.lm_solve/while"}
    ops = [Op(0, 0, 100, "k.1", None), Op(1, 0, 300, "k.1", None),
           Op(0, 100, 50, "f.2", None), Op(0, 150, 10, "copy.3", None),
           Op(0, 0, 400, "while.4", None)]  # a container: left out
    by_layer, by_kernel, by_op = attribution.op_seconds(ops, paths, 2, 0,
                                                       1000)
    assert by_layer == pytest.approx({"megba.pcg_core": 200e-9,
                                      "megba.lm_step": 25e-9, None: 5e-9})
    assert by_kernel == pytest.approx(
        {("megba.pcg_core", "coupling_expand_cam_to_edge"): 200e-9})
    assert by_op == pytest.approx({"k.1": 100e-9, "f.2": 50e-9,
                                   "copy.3": 10e-9})


def test_idle_time_is_split_by_the_innermost_span():
    ops = [Op(0, 100, 50, "a", None), Op(0, 300, 100, "b", None),
           Op(1, 0, 1000, "c", None)]  # device 1 does not count
    spans = [Span(0, 1000, tracereduce.WINDOW_SPAN),
             Span(0, 120, "megba.flat_solve"),
             Span(20, 60, "megba.phase.plan"),
             Span(120, 400, "bench.wait")]
    assert attribution.idle_stretches(ops, 0, 1000) == [(0, 100), (150, 300),
                                                      (400, 1000)]
    idle = attribution.idle_by_span(ops, spans, 0, 1000)
    assert idle == pytest.approx({"megba.flat_solve": 40e-9,  # 0-20, 80-100
                                  "megba.phase.plan": 60e-9,
                                  "bench.wait": 270e-9,  # 150-300, 400-520
                                  "no host span": 480e-9})


def test_nested_ops_are_found_by_name():
    ops = [Op(0, 0, 100, "custom.1", None), Op(0, 10, 20, "fusion.2", None),
           Op(0, 90, 30, "fusion.3", None), Op(0, 200, 10, "fusion.4", None),
           Op(0, 0, 300, "while.5", None)]  # containers are meant to nest
    (got,) = attribution.nested_ops(ops)
    assert got[:3] == ("custom", "fusion", 2)
    assert got[3] == pytest.approx(30e-9)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace recorded here: a jitted function with the program's layer
    scopes and a kernel-shaped op-name path, called inside the harness's
    window span and a `megba.flat_solve` span."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("megba.lm_solve"):
            with jax.named_scope("megba.pcg"):
                with jax.named_scope("megba.pcg_setup"):
                    x = jnp.tanh(x) + 1.0
                with jax.named_scope("megba.pcg_core"):
                    with jax.named_scope("coupling_expand_cam_to_edge"):
                        with jax.named_scope("pallas_call"):
                            x = jnp.sin(x) @ x
            with jax.named_scope("megba.lm_step"):
                return jnp.sum(jnp.cos(x))

    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("megba.flat_solve"):
                out = f(x)
            with jax.profiler.TraceAnnotation("bench.wait"):
                out.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    paths = tracereduce.scopes_from_hlo(f.lower(x).compile().as_text())
    return tracereduce.read(path, paths) + (paths,)


def test_reduce_fills_spans_kernels_and_readings(recorded):
    ops, spans, paths = recorded
    r = attribution.reduce(ops, spans, paths, devices=1, lm_iters=2,
                        pcg_iters=4)
    assert r["span_calls"]["megba.flat_solve"] == 3
    assert r["span_s"]["megba.flat_solve"] > 0
    assert r["span_s"]["bench.wait"] > 0
    assert tracereduce.WINDOW_SPAN not in r["span_s"]
    kernel = "megba.pcg_core/coupling_expand_cam_to_edge"
    assert set(r["kernel_s"]) == {kernel} and r["kernel_s"][kernel] > 0
    assert r["top_ops"] and all(len(t) == 4 for t in r["top_ops"])
    assert "megba.pcg_core" in {t[1] for t in r["top_ops"]}
    assert {"megba.pcg_setup", "megba.pcg_core",
            "megba.lm_step"} <= set(r["layer_s"])
    cov = r["coverage"]
    assert 0.5 < cov["layer_share"] <= 1.0
    assert cov["idle_s"] > 0
    assert 0.0 <= cov["idle_under_program_span"] <= 1.0
    read = r["readings"]
    assert read["host_prep_ms_per_solve"] == pytest.approx(
        r["span_s"]["megba.flat_solve"] / 3 * 1e3)
    assert read["coupling_kernels_ms_per_iter"] == pytest.approx(
        r["kernel_s"][kernel] / 4 * 1e3)
    assert read["pcg_setup_ms_per_lm"] == pytest.approx(
        r["layer_s"]["megba.pcg_setup"] / 2 * 1e3)
    assert read["lm_step_ms_per_lm"] == pytest.approx(
        r["layer_s"]["megba.lm_step"] / 2 * 1e3)
    assert attribution.reduce(ops, spans, paths, 1)["readings"][
        "lm_step_ms_per_lm"] is None  # no iteration count, no reading


def test_pcg_setup_reader_counts_the_pcg_outside_its_loop():
    read = harness.metric_reader("pcg_setup_ms_per_lm")

    def ctx(scope_s):
        s = tracereduce.Summary(devices=1, window_s=2.0, busy_s=1.5,
                                scope_s=scope_s, collective_s=0.0,
                                device_ops=[], idle_gaps=[])
        return {"trace": s, "traced_lm_iters": 10}

    # The same ops, counted under megba.pcg by a reduction that lists
    # only the outer scope, or under the inner scopes by one that lists
    # them: the same reading.
    assert read(ctx({"megba.pcg": 1.2, "megba.pcg_core": 3.0})) == \
        pytest.approx(120.0)
    assert read(ctx({"megba.pcg": 0.1, "megba.pcg_setup": 0.8,
                     "megba.pcg_backsub": 0.3, "megba.pcg_core": 3.0})) == \
        pytest.approx(120.0)
    assert read(ctx({"megba.residual_jacobian": 1.0})) is None  # no PCG
    assert read({"trace": None, "traced_lm_iters": 10}) is None
    assert read({}) is None


def test_the_tool_reads_a_rehearsal_of_the_cell(tmp_path):
    from megba_tpu import solve as solve_mod

    out_path = tmp_path / "attribution.json"
    out = io.StringIO()
    solve_mod._cached_single_solve.cache_clear()
    with redirect_stdout(out):
        rc = attribution.main(["--workload", "venice.recipe", "--seed",
                            str(2**31 + 5), "--seconds", "0.3",
                            "--rehearse", "0.002", "--out", str(out_path)])
    solve_mod._cached_single_solve.cache_clear()
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-2])["correct"] is True  # run.py's own line
    brief = json.loads(lines[-1])
    whole = json.loads(out_path.read_text())
    assert brief["readings"] == whole["readings"]
    assert whole["span_calls"]["megba.flat_solve"] == 1
    for phase in ("plan.key", "plan.gather", "lowering.transpose",
                  "dispatch.upload", "dispatch.call"):
        assert whole["span_s"]["megba.phase." + phase] > 0, phase
    assert all(v is not None for v in whole["readings"].values())
    assert whole["coverage"]["layer_share"] > 0.8
    assert whole["coverage"]["opening_gap_in_flat_solve"] > 0.5
