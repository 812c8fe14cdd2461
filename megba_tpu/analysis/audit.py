"""Compiled-program audit CLI: `python -m megba_tpu.analysis.audit`.

Gate 4 of scripts/lint.sh.  Lowers + compiles the canonical solver
programs on the CPU backend (tiny synthetic problems, no solver
execution) and runs the four audit passes of
analysis/program_audit.py; with `--check` (the default) the budget pass
compares against the committed ANALYSIS_BUDGET.json, with `--update` it
re-baselines after an intentional change.

Exit status: 0 clean, 1 violations / budget drift, 2 usage error.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from typing import Optional, Sequence


_DEVICE_COUNT_RE = re.compile(
    r"--?xla_force_host_platform_device_count=(\d+)")


def ensure_host_device_floor(flags: str, floor: int) -> str:
    """XLA_FLAGS with `--xla_force_host_platform_device_count` raised
    to at least `floor`: appended when absent, rewritten when a pre-set
    value is lower (e.g. the 2 this module exported before ba_2d_w4_f32
    existed, persisted in a dev shell or CI env), left alone when
    already sufficient."""
    m = _DEVICE_COUNT_RE.search(flags)
    if m is None:
        return (flags +
                f" --xla_force_host_platform_device_count={floor}").strip()
    if int(m.group(1)) < floor:
        return (flags[:m.start()] +
                f"--xla_force_host_platform_device_count={floor}" +
                flags[m.end():])
    return flags


def _ensure_cpu_env() -> None:
    """Pin the audit to the CPU backend with >= 4 virtual devices.

    jax is typically already *imported* here (the package __init__ pulls
    it), but the backend initialises lazily at the first device query:
    until then XLA_FLAGS (read at client creation) and
    `jax.config.jax_platforms` still take effect.  Once a backend
    exists (the pytest path — conftest configured 8 CPU devices + x64,
    which satisfies the audit) this is a no-op.
    """
    try:
        from jax._src import xla_bridge

        if xla_bridge._backends:
            return  # backend already up; caller's device config rules
    except Exception:
        pass
    # 4 devices: the 2-D canonical program (ba_2d_w4_f32) lowers on a
    # 2x2 mesh; the w2 programs use the first two.
    os.environ["XLA_FLAGS"] = ensure_host_device_floor(
        os.environ.get("XLA_FLAGS", ""), 4)
    import jax

    jax.config.update("jax_platforms", "cpu")


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m megba_tpu.analysis.audit",
        description="MegBA-TPU compiled-program auditor "
                    "(HLO transfer/collective/dtype census + AOT budget)")
    mode = parser.add_mutually_exclusive_group()
    mode.add_argument("--check", action="store_true",
                      help="fail on budget drift vs ANALYSIS_BUDGET.json "
                           "(default)")
    mode.add_argument("--update", action="store_true",
                      help="re-baseline ANALYSIS_BUDGET.json from this "
                           "run's measurements")
    parser.add_argument("--baseline", metavar="PATH", default=None,
                        help="baseline JSON path (default: the committed "
                             "ANALYSIS_BUDGET.json at the repo root)")
    parser.add_argument("--program", action="append", dest="programs",
                        metavar="NAME",
                        help="audit only this canonical program "
                             "(repeatable)")
    parser.add_argument("--summary", action="store_true",
                        help="print per-program JSON summaries")
    args = parser.parse_args(argv)

    _ensure_cpu_env()
    import jax

    if not jax.config.jax_enable_x64:
        # The f64 canonical programs (and weak-literal leaks) only exist
        # under x64; without it the dtype census would vacuously pass.
        jax.config.update("jax_enable_x64", True)
    from megba_tpu.utils.backend import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    from megba_tpu.analysis import budget as budget_mod
    from megba_tpu.analysis import program_audit

    try:
        audits = program_audit.audit_all(args.programs)
    except ValueError as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2

    failures = []
    measured = {}
    for name in sorted(audits):
        audit = audits[name]
        bad = audit.violations()
        measured[name] = audit.metrics()
        status = "FAIL" if bad else "ok"
        census = audit.pcg_body_kind_census()
        pcg = census.get("all_reduce", 0)
        extra = {k: v for k, v in census.items() if k != "all_reduce"}
        extra_s = f", pcg_body_extra={extra}" if extra else ""
        print(f"[audit] {name}: {status} "
              f"(flops={audit.flops:.3g}, bytes={audit.bytes_accessed:.3g}, "
              f"temp={audit.peak_temp_bytes:.3g}, "
              f"pcg_body_all_reduces={pcg}, "
              f"bytes_per_sp={measured[name]['collective_bytes_per_sp']:g}"
              f"{extra_s})")
        failures.extend(bad)
        if args.summary:
            import json

            print(json.dumps(audit.summary(), sort_keys=True))

    if args.update:
        meta = {"jax": jax.__version__,
                "note": "regenerate with `python -m megba_tpu.analysis."
                        "audit --update` after intentional changes"}
        if args.programs:
            # Partial update: merge into the existing baseline so the
            # unaudited programs keep their committed numbers.
            merged = budget_mod.load_baseline(args.baseline)
            merged.update(measured)
            measured = merged
        path = budget_mod.write_baseline(measured, args.baseline, meta=meta)
        print(f"[audit] baseline written: {path}")
    else:
        baseline = budget_mod.load_baseline(args.baseline)
        if not baseline:
            failures.append(
                "no ANALYSIS_BUDGET.json baseline found — run "
                "`python -m megba_tpu.analysis.audit --update` and commit "
                "the result")
        else:
            if args.programs:
                baseline = {n: v for n, v in baseline.items()
                            if n in measured}
            failures.extend(budget_mod.compare(baseline, measured))

    for f in failures:
        print(f"AUDIT VIOLATION: {f}", file=sys.stderr)
    if failures:
        print(f"{len(failures)} audit violation(s)", file=sys.stderr)
        return 1
    print("[audit] all programs within contract and budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
