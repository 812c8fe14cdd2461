"""Host spans and device scopes of a solve: PhaseTimer sub-phases, the
`megba.flat_solve` span and its phases under the profiler, the tile
statistics only a SolveReport reads, the layer scope of every compiled
instruction, and the Pallas kernel names of the program lowered for a
TPU."""

import glob
import json
import re

import jax
import numpy as np
import pytest

from megba_tpu.common import AlgoOption, ProblemOption
from megba_tpu.io.synthetic import make_synthetic_bal
from megba_tpu.ops.residuals import make_residual_jacobian_fn
from megba_tpu.solve import flat_solve
from megba_tpu.utils.timing import PhaseTimer, top_level_seconds


def _scene(cams=8, pts=120, seed=1):
    return make_synthetic_bal(cams, pts, 3.0, seed=seed)


def _solve(s, timer=None, lower_only=False, **opt):
    option = ProblemOption(dtype=np.float32,
                           algo_option=AlgoOption(max_iter=2), **opt)
    return flat_solve(make_residual_jacobian_fn(), s.cameras0, s.points0,
                      s.obs, s.cam_idx, s.pt_idx, option, use_tiled=True,
                      timer=timer, lower_only=lower_only)


# ------------------------------------------------------------ sub-phases


def test_a_subphase_is_never_counted_twice():
    from megba_tpu.observability.report import SolveReport
    from megba_tpu.observability.summarize import (
        _report_latency,
        format_report,
    )

    t = PhaseTimer()
    t.totals.update({"plan": 0.5, "plan.key": 0.2, "plan.gather": 0.1,
                     "dispatch": 0.25})
    t.counts.update({k: 1 for k in t.totals})
    phases = t.as_dict()
    assert set(phases) == {"plan", "plan.key", "plan.gather", "dispatch"}
    assert top_level_seconds(phases) == pytest.approx(0.75)
    assert t.report().splitlines()[-1] == "total: 750.0 ms over 2 phases"
    assert "plan.key: 200.0 ms" in t.report()
    rep = SolveReport(problem={}, config={}, backend={}, phases=phases,
                      result={}, trace=None, memory=None, created_unix=0.0)
    table = format_report(rep)
    assert "plan.gather: 100.0 ms" in table
    assert "total: 750.0 ms" in table
    assert _report_latency(rep) == pytest.approx(0.75)


def test_subphases_nest_inside_their_parents():
    s = _scene()
    t = PhaseTimer()
    jax.block_until_ready(_solve(s, timer=t))
    d = t.as_dict()
    for child in ("plan.key", "plan.gather", "lowering.transpose",
                  "dispatch.upload", "dispatch.call"):
        parent = child.split(".")[0]
        assert d[child]["calls"] == 1, child
        assert d[child]["total_s"] <= d[parent]["total_s"], child
    assert d["plan.key"]["total_s"] + d["plan.gather"]["total_s"] <= \
        d["plan"]["total_s"]
    assert d["dispatch.upload"]["total_s"] + d["dispatch.call"]["total_s"] \
        <= d["dispatch"]["total_s"]


def test_flat_solve_spans_nest_under_the_profiler(tmp_path):
    s = _scene()
    jax.block_until_ready(_solve(s))  # compiled: the traced call is warm
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    jax.block_until_ready(_solve(s))
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("megba."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))

    def inside(child, parent):
        return all(any(p <= a and b <= q for p, q in spans[parent])
                   for a, b in spans[child])

    assert len(spans["megba.flat_solve"]) == 1
    for parent, children in {
            "plan": ("key", "gather"),
            "lowering": ("transpose",),
            "dispatch": ("upload", "call")}.items():
        name = f"megba.phase.{parent}"
        assert inside(name, "megba.flat_solve"), name
        for c in children:
            assert len(spans[f"{name}.{c}"]) == 1, c
            assert inside(f"{name}.{c}", name), c


# ----------------------------------------------------------- tile stats


def test_tile_stats_only_when_a_report_is_written(monkeypatch, tmp_path):
    from megba_tpu.ops import segtiles

    real = segtiles.edge_stream_reuse

    def refuse(*a, **k):
        raise AssertionError("tile statistics computed with telemetry off")

    monkeypatch.delenv("MEGBA_TELEMETRY", raising=False)
    monkeypatch.setattr(segtiles, "edge_stream_reuse", refuse)
    s = _scene(12, 2500, seed=4)
    jax.block_until_ready(_solve(s))
    monkeypatch.setattr(segtiles, "edge_stream_reuse", real)
    out = tmp_path / "t.jsonl"
    jax.block_until_ready(_solve(s, telemetry=str(out)))
    (line,) = out.read_text().splitlines()
    # The block as the tree before this change wrote it for this scene,
    # and the one-hot windows: 3 edges a point, so a 1,024-slot point
    # tile spans about 341 ids and takes a 512-wide window.
    assert json.dumps(json.loads(line)["tiles"], sort_keys=True) == (
        '{"edges": 7500, "occupancy": 0.9155, "plan": "tiled_1d", '
        '"reuse_factor": 312.5, "switches": 24, "window_cam": 128, '
        '"window_pt": 512, "window_share_pt": 0.25}')


# ------------------------------------------------------ compiled program

# Computations that run as computations of their own: the entry and the
# bodies, conditions and branches of its control flow (fusion bodies and
# reducers run inside the instruction that calls them).
_CALLED = re.compile(r"(?:body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)|"
                     r"branch_computations=\{([^}]*)\}")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S+\s+"
                    r"([a-z][\w\-]*)\(")
# Bookkeeping that moves no data or only renames it.
_FREE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}


def _executed_computations(hlo):
    comps = {}
    entry = None
    for block in re.split(r"\n(?=\S)", hlo):
        m = re.match(r"(ENTRY\s+)?%?([\w.\-]+)\s", block)
        if not m:
            continue
        comps[m.group(2)] = block
        if m.group(1):
            entry = m.group(2)
    todo, seen = [entry], set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for one, group in _CALLED.findall(comps[name]):
            todo += [g.strip().lstrip("%")
                     for g in (one or group).split(",")]
    return [comps[n] for n in seen]


def test_every_compiled_instruction_has_a_layer_scope():
    hlo = _solve(_scene(), lower_only=True).compile().as_text()
    working, loose = 0, []
    for comp in _executed_computations(hlo):
        for line in comp.splitlines()[1:]:
            m = _INSTR.match(line)
            if not m or m.group(2) in _FREE:
                continue
            working += 1
            op_name = re.search(r'op_name="([^"]*)"', line)
            if op_name:
                if not re.search(r"(^|/)megba\.\w+", op_name.group(1)):
                    loose.append((m.group(1), op_name.group(1)))
            # Exceptions: XLA's own instructions carry no op_name at all
            # (buffer copies, and the CPU backend's one-op wrapper
            # fusions); everything the program wrote has one.
            elif not (m.group(2) == "copy"
                      or m.group(1).startswith("wrapped_")):
                loose.append((m.group(1), None))
    assert working > 100
    assert not loose, loose[:10]


def test_pallas_kernels_carry_their_names_into_the_tpu_program():
    from megba_tpu.algo.lm import lm_solve
    from megba_tpu.ops import segtiles

    s = _scene()
    nc, npt = s.cameras0.shape[0], s.points0.shape[0]
    plan_c, plans = segtiles.make_dual_plans(s.cam_idx, s.pt_idx, nc, npt,
                                             use_kernels=True)
    keep = plan_c.mask > 0
    obs = (s.obs[plan_c.perm] * keep[:, None]).astype(np.float32)
    pt = np.where(keep, s.pt_idx[plan_c.perm], 0).astype(np.int32)
    option = ProblemOption(dtype=np.float32)
    engine = make_residual_jacobian_fn()

    def program(cams, pts, obs, ci, pi, mask, plans):
        return lm_solve(engine, cams, pts, obs, ci, pi, mask, option,
                        cam_sorted=True, plans=plans)

    # Lowered for a TPU from here: Mosaic emits each kernel as a custom
    # call that keeps its name; nothing is compiled or run.
    with jax.enable_x64(False):
        exported = jax.export.export(jax.jit(program), platforms=["tpu"])(
            s.cameras0.T.astype(np.float32), s.points0.T.astype(np.float32),
            obs.T, plan_c.seg, pt, keep.astype(np.float32), plans)
    names = set(re.findall(r'kernel_name = "([^"]+)"',
                           exported.mlir_module()))
    assert names == {
        "jtj_grad_reduce_cam", "jtj_grad_reduce_pt",
        "coupling_expand_cam_to_edge", "coupling_expand_pt_to_edge",
        "coupling_reduce_edge_to_cam", "coupling_reduce_edge_to_pt"}
