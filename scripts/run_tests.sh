#!/bin/bash
# Full CPU test suite: static checks, every pytest test (slow ones
# included) in two processes, then end-to-end smokes.  Lanes, in order:
#
#   lint.sh              linter, strict-dtype sanitizer, compiled-program
#                        audit against ANALYSIS_BUDGET.json, concurrency
#                        and identity rules
#   pytest (two halves)  tests/test_[a-o]*.py, then tests/test_[p-z]*.py
#   observability        a telemetry-on solve writes a SolveReport the
#                        summarize CLI renders
#   fault injection      venice-10% NaN burst at a chunk resume: guards
#                        recover within rtol 1e-5 (worlds 1 and 2);
#                        unguarded the cost goes non-finite
#   serving chaos        poisoned and doomed problems in a FleetQueue:
#                        escalation heals, shedding, breaker, clean
#                        problems bitwise vs solve_many
#   triage               REJECT fails fast with no dispatch, REPAIR lands
#                        on the clean run, fleet batch-mates bitwise
#   mixed-factor fleet   four residual families in one queue, one compile
#                        per (factor, bucket), results bitwise
#   federation           artifact warm start with zero traces, worker
#                        SIGKILL reroute, results bitwise vs one host
#   elastic chaos        2-process gloo solve, rank 1 killed, rank 0
#                        resumes at world 1 (skipped without gloo)
#   network chaos        TCP fleet through a chaos proxy: partition,
#                        reconnect, deduplicated resend, worker kill
#
# The benchmark is benchmark/run.py on the chip (PERF.md), not here.
#
# A single pytest process accumulates hundreds of XLA:CPU JIT
# compilations over the full suite; on this sandbox's jaxlib that
# reproducibly segfaults inside backend_compile once the volume is
# high enough (the same tests pass in isolation or in either half —
# the crash is in the compiler's own native code, not the framework).
# Two processes keep every test exercised with headroom.
set -e -o pipefail
cd "$(dirname "$0")/.."

# Static checks first: the JAX-contract linter + strict-dtype sanitizer
# lane (scripts/lint.sh) are cheap and fail fast, so a contract
# violation can never hide behind a green unit-test run.
bash scripts/lint.sh

FIRST=$(ls tests/test_[a-o]*.py)
SECOND=$(ls tests/test_[p-z]*.py)

python -m pytest $FIRST -q -p no:cacheprovider "$@"
python -m pytest $SECOND -q -p no:cacheprovider "$@"

# Observability smoke: a tiny telemetry-on solve must produce a JSONL
# SolveReport that the summarize CLI can render (the end-to-end contract
# of megba_tpu/observability/, beyond what the unit tests pin).
SMOKE=$(mktemp /tmp/megba_obs_smoke.XXXXXX.jsonl)
trap 'rm -f "$SMOKE"' EXIT
JAX_PLATFORMS=cpu MEGBA_TELEMETRY="$SMOKE" python - <<'PY'
import numpy as np

from megba_tpu.common import AlgoOption, JacobianMode, ProblemOption, SolverOption
from megba_tpu.io.synthetic import make_synthetic_bal
from megba_tpu.ops.residuals import make_residual_jacobian_fn
from megba_tpu.solve import flat_solve

s = make_synthetic_bal(num_cameras=4, num_points=24, obs_per_point=3,
                       seed=0, param_noise=4e-2, pixel_noise=0.3,
                       dtype=np.float32)
option = ProblemOption(dtype=np.float32,
                       algo_option=AlgoOption(max_iter=3),
                       solver_option=SolverOption(max_iter=8, tol=1e-8))
res = flat_solve(make_residual_jacobian_fn(mode=JacobianMode.AUTODIFF),
                 s.cameras0, s.points0, s.obs, s.cam_idx, s.pt_idx, option)
assert res.trace is not None and int(res.iterations) > 0
PY
JAX_PLATFORMS=cpu python -m megba_tpu.observability.summarize "$SMOKE" | grep -q "phases:"
echo "observability smoke OK"

# Fault-injection smoke: venice-10% with a NaN burst seeded at GLOBAL
# LM iteration 3 — i.e. at the checkpointed driver's chunk-resume
# relinearisation, the preemption-recovery worst case.  With
# RobustOption guards the solve must recover on-device
# (status=recovered) and land within rtol 1e-5 of the clean final cost,
# single-device AND world-2; the same injection with guards off must
# yield a non-finite cost — proving the guard, not luck, did the work.
JAX_PLATFORMS=cpu python - <<'PY'
import dataclasses
import os
import tempfile

import numpy as np

# World-2 on a CPU host needs forced virtual devices, exactly as
# tests/conftest.py arranges for the pytest lanes.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

from megba_tpu.utils.backend import enable_persistent_compile_cache

enable_persistent_compile_cache()

from megba_tpu.algo.checkpointed import solve_checkpointed
from megba_tpu.common import (
    AlgoOption, ComputeKind, JacobianMode, ProblemOption, RobustOption,
    SolverOption, SolveStatus, status_name)
from megba_tpu.io.synthetic import make_synthetic_bal
from megba_tpu.ops.residuals import make_residual_jacobian_fn
from megba_tpu.robustness.faults import make_nan_burst

s = make_synthetic_bal(num_cameras=177, num_points=99392,
                       obs_per_point=5_001_946 / 993_923, seed=0,
                       param_noise=1e-2, pixel_noise=0.5, dtype=np.float32)
option = ProblemOption(
    dtype=np.float32, compute_kind=ComputeKind.IMPLICIT,
    jacobian_mode=JacobianMode.ANALYTICAL,
    algo_option=AlgoOption(max_iter=14, epsilon1=1e-12, epsilon2=1e-15),
    solver_option=SolverOption(max_iter=30, tol=1e-10, refuse_ratio=1e30))
guarded = dataclasses.replace(option, robust_option=RobustOption(guards=True))
f = make_residual_jacobian_fn(mode=JacobianMode.ANALYTICAL)
args = (f, s.cameras0, s.points0, s.obs, s.cam_idx, s.pt_idx)
plan = make_nan_burst(s.obs.shape[0], [11, 4242], start=3, stop=4)
d = tempfile.mkdtemp(prefix="megba_fault_smoke_")


def two_phase(opt, name, fault=None):
    # Phase 1 runs iterations 0-2 and snapshots; phase 2 resumes —
    # its chunk-initial relinearisation IS global iteration 3, where
    # the burst is seeded.
    ck = os.path.join(d, name + ".npz")
    short = dataclasses.replace(opt, algo_option=dataclasses.replace(
        opt.algo_option, max_iter=3))
    solve_checkpointed(*args, short, checkpoint_path=ck,
                       checkpoint_every=3, use_tiled=False)
    kw = {} if fault is None else {"fault_plan": fault}
    return solve_checkpointed(*args, opt, checkpoint_path=ck,
                              checkpoint_every=20, use_tiled=False, **kw)


for world in (1, 2):
    opt_w = dataclasses.replace(option, world_size=world)
    guard_w = dataclasses.replace(guarded, world_size=world)
    clean = two_phase(opt_w, f"clean_w{world}")
    off = two_phase(opt_w, f"off_w{world}", plan)
    assert not np.isfinite(float(off.cost)), (
        f"world {world}: guards-off injection should have poisoned the "
        f"cost, got {float(off.cost)}")
    on = two_phase(guard_w, f"on_w{world}", plan)
    gap = abs(float(on.cost) - float(clean.cost)) / abs(float(clean.cost))
    print(f"fault smoke w{world}: clean={float(clean.cost):.8e} "
          f"guarded={float(on.cost):.8e} gap={gap:.2e} "
          f"status={status_name(on.status)} recoveries={int(on.recoveries)}",
          flush=True)
    assert int(on.status) == SolveStatus.RECOVERED, status_name(on.status)
    assert gap <= 1e-5, f"world {world}: recovered cost off by {gap:.2e}"
PY
echo "fault-injection smoke OK"

# Serving chaos smoke (ISSUE 8): a 16-problem mixed fleet through a
# resilient FleetQueue — 2 NaN-poisoned problems must heal via the
# escalation ladder (RECOVERED at rung >= 1), 1 deadline-doomed problem
# must be shed before dispatch, and the 13 clean problems must land
# BITWISE at parity with an unpoisoned solve_many control (same
# batches, only the poison gate differs).  A chaos-tripped bucket must
# fail submits fast; escalated re-solves certify <= 1 compile per
# (bucket, rung) via the retrace sentinel; the dispatcher thread must
# survive all of it; and `summarize --aggregate` must render the
# retry/shed/deadline-miss/breaker counters from the report stream.
CHAOS_SINK=$(mktemp /tmp/megba_chaos_smoke.XXXXXX.jsonl)
trap 'rm -f "$SMOKE" "$CHAOS_SINK"' EXIT
JAX_PLATFORMS=cpu MEGBA_CHAOS_SINK="$CHAOS_SINK" python - <<'PY'
import dataclasses
import os

import numpy as np

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)

from megba_tpu.utils.backend import enable_persistent_compile_cache

enable_persistent_compile_cache()

from megba_tpu.analysis import retrace
from megba_tpu.common import AlgoOption, ProblemOption, SolverOption, SolveStatus
from megba_tpu.io.synthetic import make_fleet
from megba_tpu.observability import summarize
from megba_tpu.robustness.faults import (
    DispatchChaos, InjectedDispatchError, close_fault_window, make_nan_burst)
from megba_tpu.serving import (
    BreakerPolicy, BucketTripped, BucketLadder, EscalationPolicy,
    DeadlineExceeded, FleetProblem, FleetQueue, FleetStats, classify,
    solve_many)

OPT = ProblemOption(dtype=np.float64, algo_option=AlgoOption(max_iter=6),
                    solver_option=SolverOption(max_iter=12, tol=1e-10))
sink = os.environ["MEGBA_CHAOS_SINK"]

fleet = [FleetProblem.from_synthetic(s, name=f"chaos{i}")
         for i, s in enumerate(make_fleet(16, size_range=(12, 96), seed=0,
                                          dtype=np.float64))]
ladder = BucketLadder()
buckets = {}
for i, p in enumerate(fleet):
    buckets.setdefault(classify(*p.dims(), OPT.dtype, ladder), []).append(i)
# poison 2 members of the most-populated bucket (they need clean
# batch-mates to prove isolation); doom one problem from another bucket
big = max(buckets.values(), key=len)
poisoned_idx = set(big[:2])
doomed_idx = next(i for i in range(16) if i not in poisoned_idx
                  and i not in set(big))

def poison(p):
    plan = make_nan_burst(p.obs.shape[0], [1, 5], start=0, stop=1,
                          n_points=p.points.shape[0], dtype=np.float64)
    return dataclasses.replace(p, fault_plan=plan)

submitted = [poison(p) if i in poisoned_idx else p
             for i, p in enumerate(fleet)]

# --- phase 1: breaker trip + fast-fail (chaos dies pre-solve) ---------
# Two SAME-bucket problems fail consecutively (the heterogeneous fleet
# spans several buckets; the breaker is per bucket, so the trip must
# come from one bucket's own streak), then a third submit to that
# bucket must fail fast.
assert len(big) >= 3, buckets
stats = FleetStats()
chaos = DispatchChaos(fail_first=99)
with FleetQueue(OPT, max_batch=1, max_wait_s=0.0, stats=stats, chaos=chaos,
                breaker=BreakerPolicy(trip_after=2, cooldown_s=600.0)) as q:
    for i in big[:2]:
        try:
            q.submit(fleet[i]).result(timeout=60)
            raise AssertionError("injected dispatch failure did not fire")
        except InjectedDispatchError:
            pass
    try:
        q.submit(fleet[big[2]])
        raise AssertionError("tripped bucket accepted a submit")
    except BucketTripped as e:
        print("chaos smoke: tripped-bucket fast-fail OK:", e)
assert stats.breaker_trips == 1 and stats.breaker_fast_fails == 1, (
    stats.as_dict())

# --- phase 2: the mixed fleet through the resilient queue -------------
base = retrace.snapshot()
opt_tele = dataclasses.replace(OPT, telemetry=sink)
with FleetQueue(opt_tele, max_batch=16, max_wait_s=30.0, stats=stats,
                escalation=EscalationPolicy(backoff_base_s=0.01,
                                            seed=0)) as q:
    futs = []
    for i, p in enumerate(submitted):
        futs.append(q.submit(p, deadline_s=0.0 if i == doomed_idx
                             else None))
    q.flush()
    assert q._thread.is_alive(), "dispatcher thread died"
    assert all(f.done() for f in futs), "flush returned with open futures"
    results = {}
    shed = None
    for i, f in enumerate(futs):
        try:
            results[i] = f.result(timeout=1)
        except DeadlineExceeded:
            shed = i

new = {k: v - base.get(k, 0) for k, v in retrace.snapshot().items()
       if k[0].startswith("serving.batched") and v > base.get(k, 0)}
assert all(d <= 1 for d in new.values()), (
    f"duplicate batched-program trace (cache bust): {new}")
print(f"chaos smoke: {sum(new.values())} batched programs traced, "
      "<= 1 per (bucket, rung)")

assert shed == doomed_idx, f"doomed problem {doomed_idx} was not shed"
for i in poisoned_idx:
    r = results[i]
    assert r.status == int(SolveStatus.RECOVERED), (i, r.status_name)
    assert r.attempts == 2 and r.rung == 1, (r.attempts, r.rung)
    assert r.history[0]["status"] in (int(SolveStatus.STALLED),
                                      int(SolveStatus.FATAL_NONFINITE))
    assert np.isfinite(float(r.cost))
print(f"chaos smoke: {len(poisoned_idx)} poisoned problems RECOVERED "
      "via escalation")

# --- clean-problem parity: bitwise vs the unpoisoned control ----------
# Control = the same fleet minus the doomed problem, poison windows
# CLOSED: identical batch compositions and operands except the poison
# gate, so clean results must be bit-identical.
control_probs = [dataclasses.replace(
                     p, fault_plan=close_fault_window(p.fault_plan))
                 if p.fault_plan is not None else p
                 for i, p in enumerate(submitted) if i != doomed_idx]
control = solve_many(control_probs, OPT, ladder=ladder)
ctrl = {}
k = 0
for i in range(16):
    if i == doomed_idx:
        continue
    ctrl[i] = control[k]
    k += 1
clean = [i for i in range(16)
         if i not in poisoned_idx and i != doomed_idx]
assert len(clean) == 13
for i in clean:
    r, c = results[i], ctrl[i]
    assert int(r.status) == int(c.status), (i, r.status_name, c.status_name)
    assert r.cameras.tobytes() == c.cameras.tobytes(), (
        f"clean problem {i}: params drifted from the unpoisoned control")
    assert r.cost.tobytes() == c.cost.tobytes(), i
    assert not r.deadline_missed and r.attempts == 1
print("chaos smoke: 13 clean problems BITWISE at parity with the "
      "unpoisoned control")

d = stats.as_dict()
assert d["sheds"] == 1 and d["retries"] == 2, d
assert d["breaker_trips"] == 1, d

# --- aggregate CLI surfaces the resilience counters -------------------
out = summarize.aggregate_paths([sink])
print(out)
assert "status recovered: 2" in out, out
assert "2 escalated attempts (max rung 1)" in out, out
assert "2 retries" in out and "1 shed" in out, out
assert "breaker: 1 trips" in out, out
assert summarize.main(["--aggregate", sink]) == 0
PY
echo "serving chaos smoke OK"

# Triage smoke (ISSUE 10): pre-flight problem triage at venice-10%
# scale and through the fleet queue.  (1) venice-10% with injected
# degeneracies (600 deg-1 far points, 120 behind-camera points):
# REJECT must fail fast — host milliseconds, ZERO device dispatch
# (retrace sentinel + no dispatch phase) — and REPAIR must converge
# within 1e-5 of the clean (un-injected) run: the repairs soft-delete
# exactly the injected pathology, so the surviving system IS the clean
# one.  (2) A fleet of 16 with 3 poisoned problems through
# FleetQueue.submit(triage=...): 2 REJECTed futures resolve instantly
# (never dispatched), 1 REPAIRed problem solves in-batch, and the 13
# clean batch-mates stay BITWISE identical to a solve_many control of
# the same composition.  `summarize --aggregate` renders the triage
# counters from the report stream.
TRIAGE_SINK=$(mktemp /tmp/megba_triage_smoke.XXXXXX.jsonl)
trap 'rm -f "$SMOKE" "$CHAOS_SINK" "$TRIAGE_SINK"' EXIT
JAX_PLATFORMS=cpu python - <<'PY'
import time

import numpy as np

from megba_tpu.utils.backend import enable_persistent_compile_cache

enable_persistent_compile_cache()

from megba_tpu.analysis import retrace
from megba_tpu.common import (
    AlgoOption, ComputeKind, JacobianMode, ProblemOption, SolverOption,
    status_name)
from megba_tpu.io.synthetic import make_synthetic_bal
from megba_tpu.ops.residuals import make_residual_jacobian_fn
from megba_tpu.robustness.triage import (
    ProblemRejected, TriageAction, TriagePolicy)
from megba_tpu.solve import flat_solve
from megba_tpu.utils.timing import PhaseTimer

kw = dict(num_cameras=177, num_points=99392,
          obs_per_point=5_001_946 / 993_923, seed=0, param_noise=1e-2,
          pixel_noise=0.5, dtype=np.float32)
clean = make_synthetic_bal(**kw)
deg = make_synthetic_bal(**kw, n_orphan_points=600, n_behind_camera=120)
option = ProblemOption(
    dtype=np.float32, compute_kind=ComputeKind.IMPLICIT,
    jacobian_mode=JacobianMode.ANALYTICAL,
    algo_option=AlgoOption(max_iter=10, epsilon1=1e-12, epsilon2=1e-15),
    solver_option=SolverOption(max_iter=30, tol=1e-10, refuse_ratio=1e30))
f = make_residual_jacobian_fn(mode=JacobianMode.ANALYTICAL)

# -- REJECT: fast, typed, zero dispatch --------------------------------
base = retrace.snapshot()
timer = PhaseTimer()
t0 = time.perf_counter()
try:
    flat_solve(f, deg.cameras0, deg.points0, deg.obs, deg.cam_idx,
               deg.pt_idx, option, use_tiled=False, timer=timer,
               triage=TriagePolicy())
    raise AssertionError("degenerate venice problem was not rejected")
except ProblemRejected as e:
    wall = time.perf_counter() - t0
    counts = e.report.counts()
assert counts["under_constrained_point"] == 720, counts  # 600 + 120 starved
assert counts["behind_camera"] == 240, counts
assert "dispatch" not in timer.totals and "lowering" not in timer.totals, (
    timer.totals)
assert retrace.snapshot() == base, "REJECT traced a program"
assert wall < 30.0, f"REJECT took {wall:.1f}s (want host-side fast-fail)"
print(f"triage smoke: venice-10% REJECT in {wall * 1e3:.0f} ms, "
      f"zero dispatch, findings {counts}")

# -- REPAIR: converges to the clean run --------------------------------
rc = flat_solve(f, clean.cameras0, clean.points0, clean.obs, clean.cam_idx,
                clean.pt_idx, option, use_tiled=False)
rr = flat_solve(f, deg.cameras0, deg.points0, deg.obs, deg.cam_idx,
                deg.pt_idx, option, use_tiled=False,
                triage=TriagePolicy(on_degenerate=TriageAction.REPAIR))
gap = abs(float(rr.cost) - float(rc.cost)) / abs(float(rc.cost))
print(f"triage smoke: clean={float(rc.cost):.8e} "
      f"repaired={float(rr.cost):.8e} gap={gap:.2e} "
      f"status={status_name(rr.status)}")
assert gap <= 1e-5, f"triaged REPAIR cost off the clean run by {gap:.2e}"
PY
JAX_PLATFORMS=cpu MEGBA_TRIAGE_SINK="$TRIAGE_SINK" python - <<'PY'
import dataclasses
import os

import numpy as np

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)

from megba_tpu.utils.backend import enable_persistent_compile_cache

enable_persistent_compile_cache()

from megba_tpu.common import AlgoOption, ProblemOption, SolverOption
from megba_tpu.io.synthetic import make_fleet
from megba_tpu.observability import summarize
from megba_tpu.robustness.triage import (
    ProblemRejected, TriageAction, TriagePolicy, triage_problem)
from megba_tpu.serving import (
    BucketLadder, FleetProblem, FleetQueue, FleetStats, classify,
    solve_many)

OPT = ProblemOption(dtype=np.float64, algo_option=AlgoOption(max_iter=6),
                    solver_option=SolverOption(max_iter=12, tol=1e-10))
sink = os.environ["MEGBA_TRIAGE_SINK"]

fleet = [FleetProblem.from_synthetic(s, name=f"triage{i}")
         for i, s in enumerate(make_fleet(16, size_range=(12, 96), seed=0,
                                          dtype=np.float64))]
ladder = BucketLadder()
buckets = {}
for i, p in enumerate(fleet):
    buckets.setdefault(classify(*p.dims(), OPT.dtype, ladder), []).append(i)
big = max(buckets.values(), key=len)
assert len(big) >= 2, buckets


def poison(p):
    # Append one deg-1 point: same bucket class is NOT required for the
    # reject pair (they never join a batch), and the repair pair keeps
    # its bucket if the point/edge counts stay under the rungs.
    pts = np.concatenate([p.points, [[0.05, 0.05, 0.05]]])
    return dataclasses.replace(
        p, points=pts,
        cam_idx=np.concatenate([p.cam_idx, [0]]).astype(np.int32),
        pt_idx=np.concatenate([p.pt_idx,
                               [p.points.shape[0]]]).astype(np.int32),
        obs=np.concatenate([p.obs, [[0.0, 0.0]]]))


reject_idx = [i for i in range(16) if i not in big][:2]
assert len(reject_idx) == 2, buckets
# The repaired problem must stay in its CLEAN batch-mates' bucket after
# the poison appends a point+edge, or the in-batch isolation claim is
# vacuous.
repair_idx = next(
    i for i in big
    if classify(*poison(fleet[i]).dims(), OPT.dtype, ladder)
    == classify(*fleet[i].dims(), OPT.dtype, ladder))
poisoned = set(reject_idx) | {repair_idx}
submitted = [poison(p) if i in poisoned else p for i, p in enumerate(fleet)]

stats = FleetStats()
opt_tele = dataclasses.replace(OPT, telemetry=sink)
results = {}
with FleetQueue(opt_tele, max_batch=16, max_wait_s=30.0, stats=stats) as q:
    futs = {}
    for i, p in enumerate(submitted):
        if i in reject_idx:
            futs[i] = q.submit(p, triage=TriagePolicy())
            assert futs[i].done(), "rejected future not resolved at submit"
        elif i == repair_idx:
            futs[i] = q.submit(p, triage=TriagePolicy(
                on_degenerate=TriageAction.REPAIR))
        else:
            futs[i] = q.submit(p)
    q.flush()
    for i, fu in futs.items():
        if i in reject_idx:
            try:
                fu.result()
                raise AssertionError(f"problem {i} was not rejected")
            except ProblemRejected:
                pass
        else:
            results[i] = fu.result(timeout=60)
assert stats.triage_rejected == 2 and stats.triage_repaired == 1, (
    stats.as_dict())
print(f"triage smoke: 2 rejected at submit, 1 repaired in-batch "
      f"({stats.triage_points_fixed} pts fixed, "
      f"{stats.triage_edges_masked} edges masked)")

# Control: the same composition built by hand — rejected problems
# dropped, the repaired one hand-repaired — so batches match exactly
# and the 13 clean problems must be BITWISE identical.
out = triage_problem(
    submitted[repair_idx].cameras, submitted[repair_idx].points,
    submitted[repair_idx].obs, submitted[repair_idx].cam_idx,
    submitted[repair_idx].pt_idx,
    TriagePolicy(on_degenerate=TriageAction.REPAIR))
hand = dataclasses.replace(
    submitted[repair_idx], edge_mask=out.repair.edge_mask,
    cam_fixed=out.repair.cam_fixed, pt_fixed=out.repair.pt_fixed,
    health=out.report.to_dict())
control_probs, control_ids = [], []
for i in range(16):
    if i in reject_idx:
        continue
    control_probs.append(hand if i == repair_idx else submitted[i])
    control_ids.append(i)
control = dict(zip(control_ids, solve_many(control_probs, OPT,
                                           ladder=ladder)))
clean_ids = [i for i in range(16) if i not in poisoned]
assert len(clean_ids) == 13
for i in clean_ids:
    r, c = results[i], control[i]
    assert r.cameras.tobytes() == c.cameras.tobytes(), (
        f"clean problem {i} drifted next to a repaired batch-mate")
    assert r.cost.tobytes() == c.cost.tobytes(), i
r, c = results[repair_idx], control[repair_idx]
assert r.cameras.tobytes() == c.cameras.tobytes(), "repair != hand-repair"
assert np.isfinite(float(r.cost))
print("triage smoke: 13 clean batch-mates BITWISE identical to control, "
      "queue repair == hand repair")

out_text = summarize.aggregate_paths([sink])
print(out_text)
assert "triage: 2 rejected / 1 repaired" in out_text, out_text
assert "1 points fixed" in out_text and "1 edges masked" in out_text, out_text
assert "under_constrained_point=1" in out_text, out_text
PY
echo "triage smoke OK"

# Mixed-factor fleet smoke (ISSUE 13): the factor registry's
# servability contract.  A fleet mixing FOUR residual families — rig
# BA (shared body extrinsic, repeated (body, point) pairs), full-
# intrinsics radial pinhole, GPS/IMU-style unary pose priors, and BAL —
# rides ONE FleetQueue: problems must group per (factor, shape class)
# (a bucket is one residual family by construction), every result must
# come back terminal, the whole fleet must respect the <= 1 compile per
# (factor, bucket) retrace budget, a REPEATED fleet must trace NOTHING,
# and every problem must land BITWISE identical to its per-factor
# solve_many control (cross-factor batching changes scheduling, never
# answers).
JAX_PLATFORMS=cpu python - <<'PY'
import os

import numpy as np

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)

from megba_tpu.utils.backend import enable_persistent_compile_cache

enable_persistent_compile_cache()

from megba_tpu.analysis import retrace
from megba_tpu.common import AlgoOption, ProblemOption, SolverOption, SolveStatus
from megba_tpu.factors.priors import make_synthetic_priors
from megba_tpu.factors.radial import make_synthetic_radial
from megba_tpu.factors.rig import make_synthetic_rig
from megba_tpu.io.synthetic import make_synthetic_bal
from megba_tpu.serving import FleetProblem, FleetQueue, solve_many
from megba_tpu.serving.batcher import _group_by_bucket
from megba_tpu.serving.shape_class import BucketLadder

OPT = ProblemOption(dtype=np.float64, algo_option=AlgoOption(max_iter=6),
                    solver_option=SolverOption(max_iter=20, tol=1e-9))


def fleet():
    probs = []
    for i in range(3):
        probs.append(FleetProblem.from_synthetic(
            make_synthetic_rig(seed=i), name=f"rig{i}", factor="rig"))
        probs.append(FleetProblem.from_synthetic(
            make_synthetic_radial(seed=i), name=f"rad{i}",
            factor="pinhole_radial"))
        s = make_synthetic_priors(seed=i)
        probs.append(FleetProblem(
            cameras=s.cameras0, points=s.points0, obs=s.obs,
            cam_idx=s.cam_idx, pt_idx=s.pt_idx, name=f"pri{i}",
            factor="pose_prior"))
        probs.append(FleetProblem.from_synthetic(
            make_synthetic_bal(seed=i), name=f"bal{i}"))
    return probs


probs = fleet()
groups = _group_by_bucket(probs, OPT, BucketLadder())
for (sc, dims, factor), items in groups.items():
    assert {p.factor for _, p in items} == {factor}, (sc, factor)
factors_seen = {factor for (_, _, factor) in groups}
assert factors_seen == {"rig", "pinhole_radial", "pose_prior", "bal"}, (
    factors_seen)
print(f"mixed-factor smoke: {len(probs)} problems -> {len(groups)} "
      f"(factor, bucket) groups across {len(factors_seen)} families")

base = retrace.snapshot()
with FleetQueue(OPT, max_batch=4, max_wait_s=0.01) as q:
    futs = [q.submit(p) for p in probs]
    q.flush()
    queued = [f.result(timeout=600) for f in futs]
new = {k: v - base.get(k, 0) for k, v in retrace.snapshot().items()
       if k[0].startswith("serving.batched") and v > base.get(k, 0)}
assert all(d <= 1 for d in new.values()), (
    f"duplicate batched-program trace (cross-factor cache bust): {new}")
terminal = {int(SolveStatus.CONVERGED), int(SolveStatus.MAX_ITER),
            int(SolveStatus.RECOVERED)}
assert all(int(r.status) in terminal for r in queued), [
    (r.name, r.status_name) for r in queued]
print(f"mixed-factor smoke: {sum(new.values())} programs traced "
      "(<= 1 per (factor, bucket)), all results terminal")

# a repeated fleet is compile-free: every (factor, bucket) program hot
base2 = retrace.snapshot()
repeat = solve_many(fleet(), OPT)
new2 = {k: v - base2.get(k, 0) for k, v in retrace.snapshot().items()
        if v > base2.get(k, 0)}
assert not new2, f"repeat mixed fleet traced: {new2}"
print("mixed-factor smoke: repeated fleet traced ZERO programs")

# batch-mates bitwise vs per-factor solve_many controls
by_name = {r.name: r for r in queued}
for factor in sorted(factors_seen):
    sub = [p for p in fleet() if p.factor == factor]
    control = solve_many(sub, OPT)
    for p, c in zip(sub, control):
        r = by_name[p.name]
        assert r.cameras.tobytes() == c.cameras.tobytes(), (
            f"{p.name}: mixed-fleet params drifted from the "
            f"per-factor control")
        assert r.points.tobytes() == c.points.tobytes(), p.name
        assert np.asarray(r.cost).tobytes() == np.asarray(
            c.cost).tobytes(), p.name
print("mixed-factor smoke: every problem BITWISE identical to its "
      "per-factor solve_many control")
PY
echo "mixed-factor fleet smoke OK"

# Federation smoke (ISSUE 12): the scale-out tier end to end.  A
# 16-problem mixed f64 fleet is first solved single-host (the control)
# through a CompilePool that then EXPORTS its working set — manifest +
# serialized executables (portable compiles, see
# serving/compile_pool._portable_compile_scope).  A 2-worker
# FleetRouter warms from those artifacts: every bucket must load
# (mode=artifact, zero compiles) and the first fleet must dispatch with
# ZERO traces (worker-side retrace-sentinel certification).  One worker
# is then SIGKILLed mid-fleet — a real host loss: its in-flight
# problems must re-route to the survivor (typed counters), flush() must
# return with every future resolved (the no-wedge gate), and all 16
# results must be BITWISE identical to the single-host control
# (shape-class padding exactness makes federated placement
# result-invariant).  `summarize --aggregate` must render the
# federation block from the merged telemetry streams.
#
# The observability PLANE (ISSUE 16) rides the same smoke with all
# three knobs armed: the router must harvest a merged Prometheus-ready
# metrics snapshot from itself + the surviving worker (bitwise-
# deterministic across repeated idle pulls), the trace recorder must
# export ONE merged Chrome/Perfetto trace-event JSON spanning router
# and worker pids (worker spans ride the RPC replies home), and the
# w1 SIGKILL must leave a flight-recorder dump on disk.  The
# concurrency stress (ISSUE 17) then solves a 200-problem fleet while
# 4 reader threads hammer metrics_snapshot() over the shared RPC
# stream and router lock — the live regression behind the guarded-by /
# lock-order / blocking-under-lock contracts in lint lane 6.
FED_DIR=$(mktemp -d /tmp/megba_federation_smoke.XXXXXX)
trap 'rm -f "$SMOKE" "$CHAOS_SINK" "$TRIAGE_SINK"; rm -rf "$FED_DIR"' EXIT
JAX_PLATFORMS=cpu MEGBA_FED_DIR="$FED_DIR" \
MEGBA_METRICS=1 MEGBA_TRACE=1 MEGBA_FLIGHT="$FED_DIR/flight.jsonl" \
  python - <<'PY'
import json
import os
import signal
import time

import numpy as np

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)

from megba_tpu.utils.backend import enable_persistent_compile_cache

enable_persistent_compile_cache()

from megba_tpu.common import AlgoOption, ProblemOption, SolverOption
from megba_tpu.io.synthetic import make_fleet
from megba_tpu.observability import summarize
from megba_tpu.ops.residuals import make_residual_jacobian_fn
from megba_tpu.serving import (
    CompilePool, FleetProblem, FleetRouter, FleetStats, solve_many)

work = os.environ["MEGBA_FED_DIR"]
OPT = ProblemOption(dtype=np.float64, algo_option=AlgoOption(max_iter=6),
                    solver_option=SolverOption(max_iter=12, tol=1e-10))
engine = make_residual_jacobian_fn(mode=OPT.jacobian_mode)
fleet = [FleetProblem.from_synthetic(s, name=f"fed{i}")
         for i, s in enumerate(make_fleet(16, size_range=(12, 96), seed=0,
                                          dtype=np.float64))]

# -- exporter service: the control solve + the working-set export ------
store_root = os.path.join(work, "artifacts")
stats = FleetStats()
pool = CompilePool(stats=stats, artifacts=store_root)
control = solve_many(fleet, OPT, pool=pool, stats=stats)
manifest = os.path.join(work, "manifest.json")
pool.save_manifest(manifest, option=OPT)
t0 = time.perf_counter()
n_exported = pool.export_artifacts(engine, OPT)
print(f"federation smoke: exported {n_exported} bucket executables in "
      f"{time.perf_counter() - t0:.1f}s")
assert n_exported >= 3, n_exported

# -- fresh replicas: 2 workers, millisecond-class warm, zero traces ----
sink = os.path.join(work, "telemetry.jsonl")
t0 = time.perf_counter()
router = FleetRouter(OPT, n_workers=2, artifacts=store_root,
                     manifest=manifest, strict_manifest=True,
                     telemetry=sink)
up_s = time.perf_counter() - t0
d0 = router.stats.as_dict()
for wid, cs in d0["cold_start"].items():
    assert cs["mode"] == "artifact", (wid, cs)
    assert cs["artifact_compiles"] == 0, (wid, cs)
loads = sum(cs["artifact_loads"] for cs in d0["cold_start"].values())
print(f"federation smoke: 2 workers artifact-warmed in {up_s:.1f}s "
      f"({loads} executables loaded, 0 compiled)")

# -- a real host loss mid-fleet ----------------------------------------
# submit_many: the fleet enqueues ATOMICALLY, so batch composition
# reproduces the exporter's solve_many batches exactly and the
# zero-trace assertion below cannot flake on a mid-submission partial
# pick (a different lane rung would miss the store and compile).
# Kill IMMEDIATELY after: nothing has resolved yet, several buckets
# are pending, so w1's serve thread is guaranteed to pick a batch and
# hit the dead pipe — deterministic reroutes >= 1 with no sleep race.
futs = router.submit_many(fleet)
victim = router.workers["w1"]
os.kill(victim.pid, signal.SIGKILL)
t0 = time.perf_counter()
router.flush()  # the no-wedge gate: returns with every future resolved
flush_s = time.perf_counter() - t0
results = [f.result(timeout=5) for f in futs]  # none may raise

# -- observability plane: merged metrics snapshot, idle-pull determinism
# (before close(): the pull needs the surviving worker's RPC alive) ----
from megba_tpu.observability import metrics as obs_metrics

snap = router.metrics_snapshot()
assert snap is not None, "metrics_snapshot returned None with plane armed"
assert obs_metrics.snapshot_to_json(snap) == \
    obs_metrics.snapshot_to_json(router.metrics_snapshot()), (
    "metrics_snapshot drifted between two pulls on an idle fleet")
prom = obs_metrics.render_prometheus(snap)
for series in ("megba_fleet_batch_latency_seconds_bucket{",
               "megba_solve_lm_iterations_bucket{",
               "megba_fed_dispatch_total{",
               "megba_fed_worker_lost_total{"):
    assert series in prom, f"missing {series!r} in merged exposition"
n_series = sum(1 for l in prom.splitlines() if not l.startswith("#"))
print(f"federation smoke: merged metrics snapshot OK "
      f"({len(snap['metrics'])} families, {n_series} samples, "
      "2 idle pulls bitwise-equal)")

# -- concurrency stress (ISSUE 17): 200-problem fleet with the metrics
# plane pulled concurrently from 4 reader threads WHILE solving.  The
# pulls ride the same RPC stream as dispatch (WorkerHandle.request's
# ticket-turn ordering) and the same router lock as the dispatch
# bookkeeping (the guarded-by contracts) — a regression in either
# wedges flush or kills a serve thread, and a snapshot race shows up
# as a malformed/None pull.  All buckets are warm: zero compiles. -----
import threading as _threading

stress = [FleetProblem.from_synthetic(s, name=f"stress{i}")
          for i, s in enumerate(
              s for _ in range(13)
              for s in make_fleet(16, size_range=(12, 96), seed=0,
                                  dtype=np.float64))][:200]
stop_pulls = _threading.Event()
pull_errs = []
pull_counts = [0] * 4

def _puller(slot):
    while not stop_pulls.is_set():
        try:
            s = router.metrics_snapshot()
            assert s is not None and "metrics" in s, s
            pull_counts[slot] += 1
        except Exception as exc:  # noqa: BLE001 - collected and re-raised
            pull_errs.append(f"reader {slot}: {type(exc).__name__}: {exc}")
            return
        time.sleep(0.02)

readers = [_threading.Thread(target=_puller, args=(i,), daemon=True)
           for i in range(4)]
t0 = time.perf_counter()
for r in readers:
    r.start()
stress_futs = router.submit_many(stress)
router.flush()
stress_results = [f.result(timeout=5) for f in stress_futs]
stop_pulls.set()
for r in readers:
    r.join(timeout=10)
assert not any(r.is_alive() for r in readers), "metrics reader wedged"
assert not pull_errs, pull_errs
assert min(pull_counts) >= 1, pull_counts
for i, r in enumerate(stress_results):
    # NOT bitwise vs control: 13 copies of one shape class co-batch
    # into lane compositions the 16-problem control never saw.  Close
    # agreement still catches cross-thread corruption cold.
    c = control[i % 16]
    assert int(r.status) == int(c.status), (r.name, r.status, c.status)
    assert np.allclose(r.cameras, c.cameras, rtol=1e-6, atol=1e-9), r.name
    assert np.allclose(r.cost, c.cost, rtol=1e-6), (r.name, r.cost, c.cost)

# Idle again: 4 concurrent pulls must merge to ONE bitwise snapshot.
idle_json = [None] * 4

def _idle_pull(slot):
    idle_json[slot] = obs_metrics.snapshot_to_json(router.metrics_snapshot())

idlers = [_threading.Thread(target=_idle_pull, args=(i,)) for i in range(4)]
for r in idlers:
    r.start()
for r in idlers:
    r.join(timeout=10)
assert all(j is not None for j in idle_json), "idle pull hung or died"
assert len(set(idle_json)) == 1, "concurrent idle pulls disagree"
print(f"federation smoke: 200-problem stress under {sum(pull_counts)} "
      f"concurrent metric pulls in {time.perf_counter() - t0:.1f}s, "
      "4 idle pulls bitwise-equal, 200/200 results match control")

router.close()
d = router.stats.as_dict()
assert d["workers_lost"] == 1 and d["lost_workers"] == ["w1"], d
assert d["reroutes"] >= 1, d
assert sum(d["problems_by_worker"].values()) == 216, d  # 16 + 200 stress
assert d["first_solve"]["w0"]["traces"] == 0, d["first_solve"]
for r, c in zip(results, control):
    assert r.cameras.tobytes() == c.cameras.tobytes(), r.name
    assert r.cost.tobytes() == c.cost.tobytes(), r.name
    assert int(r.status) == int(c.status), r.name
print(f"federation smoke: w1 SIGKILLed mid-fleet, {d['reroutes']} problems "
      f"rerouted, flush returned in {flush_s:.1f}s, 16/16 BITWISE vs the "
      "single-host solve_many control")

# -- merged Chrome/Perfetto trace export (router + worker pids) --------
from megba_tpu.observability import spans as obs_spans

trace_path = os.path.join(work, "trace.json")
recorded = obs_spans.default_recorder().drain()
assert recorded, "no spans recorded with MEGBA_TRACE armed"
obs_spans.write_chrome_trace(trace_path, recorded)
with open(trace_path) as fh:
    doc = json.load(fh)
events = doc["traceEvents"]
assert events and all("ph" in e and "pid" in e for e in events), "bad events"
names = {e["name"] for e in events if e["ph"] == "X"}
assert "fed_dispatch" in names and "worker_solve" in names, names
procs = {e["args"]["name"] for e in events if e["ph"] == "M"}
assert len(procs) >= 2, f"trace spans only {procs} — worker spans missing"
traces = {e["args"]["trace_id"] for e in events
          if e["ph"] == "X" and "trace_id" in e.get("args", {})}
print(f"federation smoke: merged trace OK ({len(events)} events across "
      f"{sorted(procs)}, {len(traces)} traces)")

# -- flight-recorder dump left by the w1 host loss ---------------------
from megba_tpu.observability import flight as obs_flight

dumps = obs_flight.load_dumps(os.environ["MEGBA_FLIGHT"])
assert dumps, "no flight dump on disk after the w1 SIGKILL"
assert any(dmp["reason"].startswith("worker_lost") for dmp in dumps), (
    [dmp["reason"] for dmp in dumps])
kinds = {e["kind"] for dmp in dumps for e in dmp["events"]}
assert "worker_lost" in kinds, kinds
print(f"federation smoke: flight dump OK ({len(dumps)} dump(s), "
      f"kinds={sorted(kinds)})")

# -- aggregate + fleet CLI render the merged telemetry streams ---------
streams = [p for p in (sink, sink + ".w0", sink + ".w1")
           if os.path.exists(p)]
out = summarize.aggregate_paths(streams)
print(out)
assert "1 workers lost" in out, out
assert "rerouted" in out, out
assert "cold start w0: artifact" in out, out
assert "first solve 0 traces" in out, out
fleet_out = summarize.fleet_paths(streams)
print(fleet_out)
assert "traced:" in fleet_out, (
    "fleet table shows no traced solves with MEGBA_TRACE armed")
PY
echo "federation smoke OK"

# Elastic chaos smoke (ISSUE 9): a REAL 2-process gloo solve on the
# venice-10% configuration (f64), rank 1 SIGKILL'd the moment the first
# world-2 snapshot lands.  Rank 0 must surface a typed WorkerLost
# within the watchdog budget (latency asserted), resume at world 1 from
# the schema-v3 snapshot via resume_elastic, EXIT 0 on its own (the
# harness's survivor wait is the no-wedge gate), and match the
# uninterrupted world-2 run at rtol 1e-6 on cost+params with equal
# SolveStatus.  `summarize --aggregate` must render the elastic
# counters from the telemetry stream.  Gated on the same gloo probe as
# the multi-process pytest lane: a jaxlib without CPU collectives skips
# loudly instead of failing.
if JAX_PLATFORMS=cpu python -c "import sys
from megba_tpu.parallel.multihost import cpu_cross_process_collectives_available
sys.exit(0 if cpu_cross_process_collectives_available() else 3)"; then
ELASTIC_DIR=$(mktemp -d /tmp/megba_elastic_smoke.XXXXXX)
trap 'rm -f "$SMOKE" "$CHAOS_SINK" "$TRIAGE_SINK"; rm -rf "$FED_DIR" "$ELASTIC_DIR"' EXIT
JAX_PLATFORMS=cpu MEGBA_ELASTIC_DIR="$ELASTIC_DIR" python - <<'PY'
import importlib.util
import os
import re
import socket
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)

from megba_tpu.utils.backend import enable_persistent_compile_cache

enable_persistent_compile_cache()

import numpy as np

from megba_tpu.observability import summarize
from megba_tpu.robustness.harness import run_world_until_snapshot_then_kill
from megba_tpu.utils.checkpoint import load_state

work = os.environ["MEGBA_ELASTIC_DIR"]
repo = os.getcwd()
worker = os.path.join(repo, "tests", "_elastic_worker.py")

with socket.socket() as s:
    s.bind(("localhost", 0))
    port = s.getsockname()[1]

hb = os.path.join(work, "hb")
ck0 = os.path.join(work, "ck.r0.npz")
ck1 = os.path.join(work, "ck.r1.npz")
out0 = os.path.join(work, "result.npz")
sink = os.path.join(work, "telemetry.jsonl")
env = dict(os.environ)
env.pop("XLA_FLAGS", None)  # each worker pins its own single device
env["JAX_PLATFORMS"] = "cpu"
env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
env["MEGBA_TELEMETRY"] = sink


def argv(rank, ck, out):
    return [sys.executable, worker, str(rank), str(port), "2", ck, out,
            "venice10", hb]


t0 = time.monotonic()
outcome = run_world_until_snapshot_then_kill(
    [argv(0, ck0, out0), argv(1, ck1, "-")], ck0, kill_rank=1,
    rendezvous_argv=[sys.executable, "-m", "megba_tpu.parallel.multihost",
                     "--serve", str(port), "2"],
    timeout=1800.0, survivor_timeout=1800.0, env=env)
print(f"elastic smoke: world-2 venice-10% ran {time.monotonic() - t0:.1f}s, "
      f"rcs={outcome.returncodes}")
assert outcome.returncodes[1] < 0, outcome.outputs[1]
assert outcome.returncodes[0] == 0, outcome.outputs[0]
out = outcome.outputs[0]
m = re.search(r"ELASTIC-DETECT kind=(\w+) latency=([0-9.]+) "
              r"budget=([0-9.]+)", out)
assert m, f"no detection line:\n{out}"
kind, latency, budget = m.group(1), float(m.group(2)), float(m.group(3))
assert kind == "worker_lost", out
assert latency <= budget, (latency, budget)
print(f"elastic smoke: rank 1 loss detected in {latency:.3f}s "
      f"(watchdog budget {budget:.0f}s)")
assert "ELASTIC-RESUME world=1" in out, out
assert int(load_state(ck0)["world_size"]) == 1

# Parity vs the uninterrupted world-2 run (single-process, virtual
# devices: same mesh size, same program, same collectives as the
# 2-process world — the equivalence test_multihost.py pins).
spec = importlib.util.spec_from_file_location("_elastic_worker", worker)
ew = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ew)
from megba_tpu.algo.checkpointed import solve_checkpointed
from megba_tpu.common import JacobianMode
from megba_tpu.ops.residuals import make_residual_jacobian_fn

s, option = ew.build_problem("venice10", 2)
f = make_residual_jacobian_fn(mode=JacobianMode.ANALYTICAL)
ref = solve_checkpointed(
    f, s.cameras0, s.points0, s.obs, s.cam_idx, s.pt_idx, option,
    checkpoint_path=os.path.join(work, "clean.npz"),
    checkpoint_every=ew.CHECKPOINT_EVERY, use_tiled=False)
res = dict(np.load(out0))
assert int(res["status"]) == int(ref.status), (
    int(res["status"]), int(ref.status))
np.testing.assert_allclose(float(res["cost"]), float(ref.cost), rtol=1e-6)
np.testing.assert_allclose(res["cameras"], np.asarray(ref.cameras),
                           rtol=1e-6, atol=1e-9)
np.testing.assert_allclose(res["points"], np.asarray(ref.points),
                           rtol=1e-6, atol=1e-9)
gap = abs(float(res["cost"]) - float(ref.cost)) / abs(float(ref.cost))
print(f"elastic smoke: shrink-world parity OK "
      f"(cost relgap {gap:.2e}, status {int(ref.status)})")

agg = summarize.aggregate_paths([sink])
print(agg)
assert "1 workers lost" in agg and "1 resumes" in agg, agg
assert "time-to-detection" in agg, agg
PY
echo "elastic chaos smoke OK"
else
echo "elastic chaos smoke SKIPPED: jaxlib CPU client lacks gloo collectives"
fi

# Network-chaos federation smoke (ISSUE 20): the multi-host transport
# under a hostile network.  The SAME 16-problem fleet is solved three
# ways — single-host solve_many (control), a 2-worker PIPE fleet, and
# a 2-worker TCP fleet whose workers dial the router THROUGH a
# deterministic chaos proxy (robustness/netfaults.py) — and all three
# must agree BITWISE (shape-class padding exactness makes the carrier
# result-invariant).  Mid-flight the proxy PARTITIONS the fleet while
# a cold-bucket solve is executing on a worker: the worker's reply
# send dies, it re-dials under seeded backoff (refused until heal),
# re-registers with `resume`, and the router's stranded reader resends
# the SAME sequence id — which the worker answers from its reply cache
# (the dedup counter is asserted: a resend can never double-solve).
# One worker is then SIGKILLed — a real host loss, distinct from the
# connection loss above: its problems must re-route to the survivor,
# typed and counted, and flush() must return with every future
# resolved (the no-wedge gate).  Every transport event must land in
# all three observability planes (metrics, spans, flight ring).
NETFED_DIR=$(mktemp -d /tmp/megba_netchaos_smoke.XXXXXX)
trap 'rm -f "$SMOKE" "$CHAOS_SINK" "$TRIAGE_SINK"; rm -rf "$FED_DIR" ${ELASTIC_DIR:+"$ELASTIC_DIR"} "$NETFED_DIR"' EXIT
JAX_PLATFORMS=cpu MEGBA_NETFED_DIR="$NETFED_DIR" \
MEGBA_METRICS=1 MEGBA_TRACE=1 MEGBA_FLIGHT="$NETFED_DIR/flight.jsonl" \
  python - <<'PY'
import os
import signal
import socket
import time

import numpy as np

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)

from megba_tpu.utils.backend import enable_persistent_compile_cache

enable_persistent_compile_cache()

from megba_tpu.common import AlgoOption, ProblemOption, SolverOption
from megba_tpu.io.synthetic import make_fleet
from megba_tpu.observability import metrics as obs_metrics
from megba_tpu.ops.residuals import make_residual_jacobian_fn
from megba_tpu.robustness.netfaults import ChaosTcpProxy
from megba_tpu.serving.transport import ReconnectPolicy
from megba_tpu.serving import (
    CompilePool, FleetProblem, FleetRouter, FleetStats, solve_many)

work = os.environ["MEGBA_NETFED_DIR"]
OPT = ProblemOption(dtype=np.float64, algo_option=AlgoOption(max_iter=6),
                    solver_option=SolverOption(max_iter=12, tol=1e-10))
engine = make_residual_jacobian_fn(mode=OPT.jacobian_mode)
fleet = [FleetProblem.from_synthetic(s, name=f"net{i}")
         for i, s in enumerate(make_fleet(16, size_range=(12, 96), seed=3,
                                          dtype=np.float64))]

# -- single-host control + artifact export (millisecond worker warms) --
store = os.path.join(work, "artifacts")
stats = FleetStats()
pool = CompilePool(stats=stats, artifacts=store)
control = solve_many(fleet, OPT, pool=pool, stats=stats)
manifest = os.path.join(work, "manifest.json")
pool.save_manifest(manifest, option=OPT)
n_exported = pool.export_artifacts(engine, OPT)
print(f"network chaos smoke: exported {n_exported} bucket executables")

# A cold bucket the manifest does NOT cover (the 16-fleet's sizes pad
# to <=128 points; these pad to 256): its compile-on-dispatch runs for
# seconds on the worker — a deterministic in-flight window for the
# partition below (and a live ColdDispatchWarning).
big = [FleetProblem.from_synthetic(s, name=f"cold{i}")
       for i, s in enumerate(make_fleet(2, size_range=(150, 220), seed=9,
                                        dtype=np.float64))]
big_control = solve_many(big, OPT, pool=pool, stats=stats)

# -- pipe fleet: the same-host carrier, bitwise vs control -------------
with FleetRouter(OPT, n_workers=2, artifacts=store, manifest=manifest,
                 strict_manifest=True) as pipe_router:
    pipe_futs = pipe_router.submit_many(fleet)
    pipe_router.flush()
    pipe_results = [f.result(timeout=5) for f in pipe_futs]
for r, c in zip(pipe_results, control):
    assert r.cameras.tobytes() == c.cameras.tobytes(), r.name
    assert r.cost.tobytes() == c.cost.tobytes(), r.name
    assert int(r.status) == int(c.status), r.name
print("network chaos smoke: pipe fleet 16/16 BITWISE vs solve_many")

# -- TCP fleet, every worker connection through the chaos proxy --------
# The proxy must exist before the router (workers dial THROUGH it at
# spawn), but it needs the router's port — so a probe socket picks the
# port first and the router binds it explicitly.
probe = socket.socket()
probe.bind(("127.0.0.1", 0))
port = probe.getsockname()[1]
probe.close()
proxy = ChaosTcpProxy(f"127.0.0.1:{port}")
sink = os.path.join(work, "telemetry.jsonl")
t0 = time.perf_counter()
# The reconnect window must outlive a worker-side cold compile: the
# worker can only notice the severed link and re-dial AFTER its
# in-flight solve returns, and the partitioned cold bucket below
# compiles for tens of seconds on a CPU runner.
router = FleetRouter(OPT, n_workers=2, artifacts=store, manifest=manifest,
                     strict_manifest=True, transport="tcp",
                     bind=f"127.0.0.1:{port}", advertise=proxy.address,
                     token="netchaos-smoke", telemetry=sink,
                     reconnect=ReconnectPolicy(window_s=240.0))
print(f"network chaos smoke: 2 TCP workers registered through the "
      f"proxy in {time.perf_counter() - t0:.1f}s")

futs = router.submit_many(fleet)
router.flush()
results = [f.result(timeout=5) for f in futs]
for r, c in zip(results, control):
    assert r.cameras.tobytes() == c.cameras.tobytes(), r.name
    assert r.cost.tobytes() == c.cost.tobytes(), r.name
    assert int(r.status) == int(c.status), r.name
print("network chaos smoke: TCP fleet 16/16 BITWISE vs solve_many "
      "AND the pipe fleet")


def merged_counter(name):
    snap = router.metrics_snapshot()
    fam = (snap or {}).get("metrics", {}).get(name)
    return 0 if fam is None else int(sum(fam["series"].values()))


# -- mid-flight partition during a cold-bucket solve -------------------
futs2 = router.submit_many(big)
# Partition only once the batch is IN FLIGHT (request sent, reply
# pending): the worker is then mid-compile for seconds — the reply
# send must die on the severed connection and the router must resend.
deadline = time.monotonic() + 30.0
while router._inflight < 1:
    assert time.monotonic() < deadline, "cold batch never dispatched"
    time.sleep(0.005)
time.sleep(0.3)  # let the request cross the proxy relay
proxy.partition()
time.sleep(1.2)
proxy.heal()
t0 = time.perf_counter()
router.flush()
flush_s = time.perf_counter() - t0
results2 = [f.result(timeout=5) for f in futs2]
for r, c in zip(results2, big_control):
    assert r.cameras.tobytes() == c.cameras.tobytes(), r.name
    assert r.cost.tobytes() == c.cost.tobytes(), r.name
n_reconnect = merged_counter("megba_transport_reconnect_total")
n_resend = merged_counter("megba_transport_resend_total")
n_conn_lost = merged_counter("megba_transport_conn_lost_total")
n_dedup = merged_counter("megba_transport_dedup_total")
assert n_conn_lost >= 1, "partition left no conn_lost event"
assert n_reconnect >= 1, "no worker re-registered after the heal"
assert n_resend >= 1, "stranded reader never resent its request"
assert n_dedup >= 1, ("resend was re-executed, not served from the "
                      "worker reply cache")
counts = proxy.event_counts()
assert counts["partition"] == 1 and counts["heal"] == 1, counts
assert counts.get("refused", 0) >= 1, counts  # backoff dials hit the wall
print(f"network chaos smoke: partition healed — flush in {flush_s:.1f}s, "
      f"{n_conn_lost} conn_lost / {n_reconnect} reconnects / "
      f"{n_resend} resends / {n_dedup} dedup hits, 2/2 cold-bucket "
      "results BITWISE (no double-solve), proxy "
      f"refused {counts.get('refused', 0)} dials while partitioned")

# -- a real host loss: SIGKILL one worker, reroute to the survivor -----
victim = router.workers["w1"]
os.kill(victim.pid, signal.SIGKILL)
futs3 = router.submit_many(fleet)
t0 = time.perf_counter()
router.flush()  # the no-wedge gate: pending==0 and inflight==0
flush_s = time.perf_counter() - t0
results3 = [f.result(timeout=5) for f in futs3]
for r, c in zip(results3, control):
    assert r.cameras.tobytes() == c.cameras.tobytes(), r.name
    assert r.cost.tobytes() == c.cost.tobytes(), r.name
router.close()
d = router.stats.as_dict()
assert d["workers_lost"] == 1 and d["lost_workers"] == ["w1"], d
assert d["reroutes"] >= 1, d
assert d["cold_dispatches"] >= 2, d  # the unmanifested big bucket
print(f"network chaos smoke: w1 SIGKILLed — {d['reroutes']} problems "
      f"rerouted to the survivor, flush returned in {flush_s:.1f}s, "
      "16/16 BITWISE vs control")

# -- transport events visible in spans + flight ring -------------------
from megba_tpu.observability import flight as obs_flight
from megba_tpu.observability import spans as obs_spans

recorded = obs_spans.default_recorder().drain()
span_names = {s["name"] for s in recorded}
assert any(n.startswith("transport_") for n in span_names), span_names
dumps = obs_flight.load_dumps(os.environ["MEGBA_FLIGHT"])
assert dumps, "no flight dump after the w1 SIGKILL"
kinds = {e["kind"] for dmp in dumps for e in dmp["events"]}
assert "worker_lost" in kinds, kinds
assert any(k.startswith("transport_") for k in kinds), kinds
print(f"network chaos smoke: transport events in spans "
      f"({sorted(n for n in span_names if n.startswith('transport_'))}) "
      f"and flight ring ({sorted(k for k in kinds if k.startswith('transport_'))})")
proxy.close()
PY
echo "network chaos smoke OK"
