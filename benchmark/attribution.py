"""Where a traced solve's time goes, by every name the program gives it.

    python3 benchmark/attribution.py --workload <name> --seed <n> \
        --seconds <s> [--out PATH] [--rehearse <share>]

Runs benchmark/run.py's own `--trace 1` run in this process (its result
line is printed as usual), keeps the trace that run reduced, and reduces
it once more:

- span_s: seconds of each host span inside the window (`bench.*`, the
  `megba.flat_solve` span, the PhaseTimer phases `megba.phase.*`);
- layer_s: device seconds by the innermost `megba.*` scope on each op's
  op-name path, whatever scopes the program has (tracereduce attributes
  time to its SCOPES only), and coverage: the share of op time under
  some `megba.*` scope, with the longest ops under none;
- kernel_s: device seconds per (layer, Pallas kernel), the kernel being
  the op-name component before `pallas_call`;
- idle_by_span: device 0's idle time in the window, each idle stretch
  split by the innermost host span over each part of it;
- nested: ops of device 0 that start inside another op (control-flow
  containers aside), by name: time that a sum over ops counts twice;
- top_ops: device 0's longest ops with their layer and the end of
  their op-name path, which says what each is;
- readings: what `host_prep_ms_per_solve`, `pcg_setup_ms_per_lm`,
  `lm_step_ms_per_lm` and `coupling_kernels_ms_per_iter` read from these.

One JSON line on stdout after run.py's; the idle split on stderr; the
whole reading, longer lists included, in --out.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import tracereduce  # noqa: E402
from tracereduce import CONTAINER, WINDOW_SPAN, Op, Span  # noqa: E402

PROGRAM_SPANS = ("bench.", "megba.")
FLAT_SOLVE_SPAN = "megba.flat_solve"
_LAYER = re.compile(r"(?:^|/)(megba\.\w+)(?=/|$|\))")
_KERNEL = re.compile(r"(?:^|/)([\w.\-]+)/pallas_call(?=/|$)")
_SUFFIX = re.compile(r"\.\d+$")
LONGEST = 15


def layer_of(path: Optional[str]) -> Optional[str]:
    """The innermost `megba.*` scope on an op-name path."""
    found = _LAYER.findall(path or "")
    return found[-1] if found else None


def kernel_of(path: Optional[str]) -> Optional[str]:
    """The Pallas kernel an op-name path ends in (`<name>/pallas_call`)."""
    found = _KERNEL.findall(path or "")
    return found[-1] if found else None


def window(ops: Sequence[Op], spans: Sequence[Span]) -> Tuple[float, float]:
    """The harness's `bench.traced` span, else the extent of the ops."""
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        return (min(s.start_ns for s in win),
                max(s.start_ns + s.dur_ns for s in win))
    if ops:
        return (min(o.start_ns for o in ops),
                max(o.start_ns + o.dur_ns for o in ops))
    return 0.0, 0.0


def _clip(start, dur, lo, hi) -> float:
    return max(0.0, min(start + dur, hi) - max(start, lo))


def _program_spans(spans: Sequence[Span]) -> List[Span]:
    return [s for s in spans if s.name != WINDOW_SPAN
            and s.name.startswith(PROGRAM_SPANS)]


def span_seconds(spans: Sequence[Span], lo: float, hi: float
                 ) -> Tuple[Dict[str, float], Dict[str, int]]:
    """Seconds and count of each program span inside [lo, hi]."""
    secs: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    for s in _program_spans(spans):
        t = _clip(s.start_ns, s.dur_ns, lo, hi)
        if t > 0:
            secs[s.name] += t / 1e9
            calls[s.name] += 1
    return dict(secs), dict(calls)


def op_seconds(ops: Sequence[Op], paths: Dict[str, str], devices: int,
               lo: float, hi: float):
    """Device seconds, mean over devices, by layer and by (layer,
    kernel), and device 0's seconds by op; containers left out."""
    by_layer: Dict[Optional[str], float] = defaultdict(float)
    by_kernel: Dict[Tuple[Optional[str], str], float] = defaultdict(float)
    by_op: Dict[str, float] = defaultdict(float)
    for o in ops:
        t = _clip(o.start_ns, o.dur_ns, lo, hi)
        if t <= 0 or CONTAINER.match(o.name):
            continue
        path = paths.get(o.name)
        layer, kernel = layer_of(path), kernel_of(path)
        by_layer[layer] += t / devices / 1e9
        if kernel:
            by_kernel[(layer, kernel)] += t / devices / 1e9
        if o.device == 0:
            by_op[o.name] += t / 1e9
    return dict(by_layer), dict(by_kernel), dict(by_op)


def idle_stretches(ops: Sequence[Op], lo: float, hi: float
                   ) -> List[Tuple[float, float]]:
    """Device 0's idle stretches inside [lo, hi], in order."""
    busy = sorted((max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi))
                  for o in ops if o.device == 0)
    out, prev = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > prev:
            out.append((prev, a))
        prev = max(prev, b)
    return out


def idle_by_span(ops: Sequence[Op], spans: Sequence[Span], lo: float,
                 hi: float) -> Dict[str, float]:
    """Device 0's idle seconds in [lo, hi] by the innermost (shortest)
    program span over each part of each idle stretch."""
    prog = _program_spans(spans)
    out: Dict[str, float] = defaultdict(float)
    for a, b in idle_stretches(ops, lo, hi):
        over = [s for s in prog
                if s.start_ns < b and s.start_ns + s.dur_ns > a]
        cuts = sorted({a, b} | {x for s in over
                                for x in (s.start_ns, s.start_ns + s.dur_ns)
                                if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            cover = [s for s in over
                     if s.start_ns <= mid <= s.start_ns + s.dur_ns]
            label = (min(cover, key=lambda s: s.dur_ns).name if cover
                     else "no host span")
            out[label] += (y - x) / 1e9
    return dict(out)


def nested_ops(ops: Sequence[Op]) -> List[Tuple[str, str, int, float]]:
    """(outer, inner, count, seconds) for device 0's ops that start
    inside another op, names without their `.N`; containers are never
    the outer op (their bodies are meant to nest)."""
    dev0 = sorted((o for o in ops if o.device == 0),
                  key=lambda o: (o.start_ns, -o.dur_ns))
    pairs: Dict[Tuple[str, str], List[float]] = defaultdict(
        lambda: [0, 0.0])
    for i, outer in enumerate(dev0):
        if CONTAINER.match(outer.name):
            continue
        end = outer.start_ns + outer.dur_ns
        for inner in dev0[i + 1:]:
            if inner.start_ns >= end:
                break
            key = (_SUFFIX.sub("", outer.name), _SUFFIX.sub("", inner.name))
            pairs[key][0] += 1
            pairs[key][1] += _clip(inner.start_ns, inner.dur_ns,
                                   outer.start_ns, end) / 1e9
    return sorted(((a, b, n, s) for (a, b), (n, s) in pairs.items()),
                  key=lambda p: -p[3])


def _top(d: Dict, n: int = LONGEST) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce(ops: Sequence[Op], spans: Sequence[Span], paths: Dict[str, str],
           devices: int, lm_iters: Optional[int] = None,
           pcg_iters: Optional[int] = None) -> dict:
    """The whole reading of one trace (see the module docstring)."""
    lo, hi = window(ops, spans)
    span_s, span_n = span_seconds(spans, lo, hi)
    by_layer, by_kernel, by_op = op_seconds(ops, paths, devices, lo, hi)
    total = sum(by_layer.values())
    scoped = sum(v for k, v in by_layer.items() if k is not None)
    idle = idle_by_span(ops, spans, lo, hi)
    idle_s = sum(idle.values())
    gaps = idle_stretches(ops, lo, hi)
    opening = gaps[0] if gaps and gaps[0][0] == lo else None
    covered = None
    if opening is not None:
        covered = sum(
            _clip(s.start_ns, s.dur_ns, *opening) for s in spans
            if s.name == FLAT_SOLVE_SPAN) / (opening[1] - opening[0])

    def per(x, units):
        return None if not units else x / units * 1e3

    def layer(name):
        return by_layer.get(name, 0.0)

    coupling = sum(v for (lay, k), v in by_kernel.items()
                   if lay == "megba.pcg_core" and k.startswith("coupling_"))
    prep = span_s.get(FLAT_SOLVE_SPAN)
    return {
        "window_s": (hi - lo) / 1e9,
        "span_s": span_s,
        "span_calls": span_n,
        "layer_s": {k or "-": v for k, v in by_layer.items()},
        "kernel_s": {f"{lay or '-'}/{k}": v
                     for (lay, k), v in by_kernel.items()},
        "coverage": {
            "op_s": total,
            "layer_share": scoped / total if total else None,
            "tracereduce_share": (
                sum(_clip(o.start_ns, o.dur_ns, lo, hi) for o in ops
                    if o.scope and not CONTAINER.match(o.name))
                / devices / 1e9 / total if total else None),
            "opening_gap_s": (None if opening is None
                              else (opening[1] - opening[0]) / 1e9),
            "opening_gap_in_flat_solve": covered,
            "idle_s": idle_s,
            "idle_under_program_span": (
                sum(v for k, v in idle.items() if k.startswith("megba."))
                / idle_s if idle_s else None),
            "idle_under_any_span": (
                sum(v for k, v in idle.items() if k != "no host span")
                / idle_s if idle_s else None),
        },
        "outside_layers": _top({k: v for k, v in by_op.items()
                                if layer_of(paths.get(k)) is None}),
        "top_ops": [[k, layer_of(paths.get(k)),
                     "/".join((paths.get(k) or "").split("/")[-2:]), v]
                    for k, v in _top(by_op)],
        "idle_by_span": _top(idle, 10 ** 6),
        "nested": [list(p) for p in nested_ops(ops)],
        "readings": {
            "host_prep_ms_per_solve": (
                None if prep is None else prep / span_n[FLAT_SOLVE_SPAN]
                * 1e3),
            "pcg_setup_ms_per_lm": per(
                layer("megba.pcg") + layer("megba.pcg_setup")
                + layer("megba.pcg_backsub"), lm_iters),
            "lm_step_ms_per_lm": per(
                layer("megba.lm_step") + layer("megba.lm_accept_reject"),
                lm_iters),
            "coupling_kernels_ms_per_iter": per(coupling, pcg_iters),
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="write the whole reading here as JSON")
    args, rest = ap.parse_known_args(argv)
    import harness
    import run

    kept = {}
    read, answer_of = tracereduce.read, harness.answer_of

    def keep_read(path, hlo_scopes):
        kept["ops"], kept["spans"] = read(path, hlo_scopes)
        kept["paths"] = hlo_scopes
        return kept["ops"], kept["spans"]

    def keep_answer(res):
        kept["answer"] = answer_of(res)
        return kept["answer"]

    # run.py's own run, its trace kept on the way: the same process,
    # spans and reduction as any benchmark run with --trace 1.
    tracereduce.read, harness.answer_of = keep_read, keep_answer
    try:
        rc = run.main(rest + ["--trace", "1"])
    finally:
        tracereduce.read, harness.answer_of = read, answer_of
    if rc != 0 or "ops" not in kept:
        return rc or 1
    cell = harness.load_cell(run.parse(rest + ["--trace", "1"]).workload)
    # Iterations per solve from the window's last answer: every solve of
    # a window solves the same problem from the same start.
    answer = kept["answer"]
    out = reduce(kept["ops"], kept["spans"], kept["paths"],
                 int(cell["cell"]["chips"]), answer["iterations"],
                 answer["pcg_iterations"])
    for name, sec in out["idle_by_span"]:
        run.log(f"idle under {name}: {sec:.6f} s")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    brief = {k: out[k] for k in ("window_s", "coverage", "readings")}
    brief["layer_s"] = out["layer_s"]
    brief["nested"] = out["nested"][:5]
    print(json.dumps(brief), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
