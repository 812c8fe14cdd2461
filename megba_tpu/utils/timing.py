"""Timing / profiling hooks.

The reference's only observability is wall-clock deltas printed per LM
iteration (lm_algo.cu:141,157-161,215-219).  Here: `PhaseTimer` collects
named phase timings (block_until_ready-accurate), and `trace_profile`
wraps a block in a `jax.profiler` trace for TensorBoard/Perfetto — the
TPU-native upgrade path (SURVEY.md §5.1).
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Optional

import jax

# ---------------------------------------------------------------------------
# Clock discipline.  These two helpers are the ONLY sanctioned raw-clock
# reads outside observability/ — the `raw-clock` lint rule
# (analysis/rules.py) forbids time.time()/time.perf_counter() everywhere
# else, so every latency measurement in the package goes through the
# monotonic clock (immune to NTP steps) and every timestamp that must be
# comparable across hosts is an explicit, named wall-clock read.


def monotonic_s() -> float:
    """Monotonic seconds for measuring durations (never wall clock)."""
    return time.perf_counter()


def wall_unix() -> float:
    """Unix wall-clock seconds, for report timestamps only — never for
    durations (NTP steps make wall-clock deltas lie)."""
    return time.time()


# Optional observer of completed PhaseTimer phases: the spans recorder
# (observability/spans.py) installs a hook so every timed phase joins the
# active trace as a child span.  Module-level on purpose — phases fire
# deep inside solve paths that never see a recorder object.  Hook
# signature: (name, duration_s).  Exceptions are swallowed: telemetry
# must never fail a solve.
_PHASE_HOOK: Optional[Callable[[str, float], None]] = None


def set_phase_hook(hook: Optional[Callable[[str, float], None]]) -> None:
    global _PHASE_HOOK
    _PHASE_HOOK = hook


class _Phase:
    """Handle yielded by PhaseTimer.phase; register outputs to sync on."""

    def __init__(self):
        self._targets = []

    def sync(self, x):
        """Mark `x` (array/pytree produced inside the block) to be
        block_until_ready'd before the phase's clock stops; returns x."""
        self._targets.append(x)
        return x


def is_subphase(name: str) -> bool:
    """A phase named `<parent>.<child>` is timed inside its parent's
    block: reported under its own name, never added into a total."""
    return "." in name


def top_level_seconds(phases: Dict[str, Dict[str, float]]) -> float:
    """Summed `total_s` of a `PhaseTimer.as_dict()` payload, sub-phases
    left out (their time is already inside their parent's)."""
    return sum(ph.get("total_s", 0.0) for name, ph in phases.items()
               if not is_subphase(name))


class PhaseTimer:
    """Accumulates wall-clock per named phase; device-sync aware.

    JAX dispatch is asynchronous, so an un-synced phase measures only
    dispatch time.  Register the block's outputs on the yielded handle:

        with timer.phase("pcg") as ph:
            out = ph.sync(pcg_solve(...))

    A sub-phase is named `<parent>.<child>` and timed inside the
    parent's block (`with timer.phase("plan.key"):` inside
    `with timer.phase("plan"):`); `report()` and `top_level_seconds`
    count only the parents.
    """

    def __init__(self):
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        handle = _Phase()
        # TraceAnnotation: each phase shows up as a named host-side span
        # in jax.profiler traces (trace_profile -> TensorBoard/Perfetto),
        # so the phase breakdown and the profiler timeline line up.
        with jax.profiler.TraceAnnotation(f"megba.phase.{name}"):
            t0 = time.perf_counter()
            try:
                yield handle
            finally:
                for t in handle._targets:
                    jax.block_until_ready(t)
                dt = time.perf_counter() - t0
                self.totals[name] = self.totals.get(name, 0.0) + dt
                self.counts[name] = self.counts.get(name, 0) + 1
                if _PHASE_HOOK is not None:
                    try:
                        _PHASE_HOOK(name, dt)
                    except Exception:
                        pass

    def count_event(self, name: str, n: int = 1) -> None:
        """Count an instantaneous event (zero duration) — e.g. the host
        plan cache's `plan_cache_hit` counter.  Shows up in `as_dict()`
        / `report()` with total_s 0.0 and `calls` = occurrence count, so
        the SolveReport phase schema is unchanged."""
        self.totals.setdefault(name, 0.0)
        self.counts[name] = self.counts.get(name, 0) + n

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        """{name: {total_s, calls}} — the SolveReport `phases` payload."""
        return {name: {"total_s": self.totals[name],
                       "calls": self.counts[name]}
                for name in self.totals}

    def reset(self) -> None:
        """Drop all accumulated phases (reuse one timer across solves)."""
        self.totals.clear()
        self.counts.clear()

    def report(self) -> str:
        if not self.totals:
            return "no phases recorded"
        lines = []
        for name in sorted(self.totals, key=self.totals.get, reverse=True):
            t, c = self.totals[name], self.counts[name]
            lines.append(f"{name}: {t * 1e3:.1f} ms total / {c} calls = {t / c * 1e3:.2f} ms")
        top = [n for n in self.totals if not is_subphase(n)]
        total = sum(self.totals[n] for n in top)
        lines.append(f"total: {total * 1e3:.1f} ms over {len(top)} phases")
        return "\n".join(lines)


@contextlib.contextmanager
def trace_profile(logdir: Optional[str]):
    """jax.profiler trace context; no-op when logdir is None."""
    if logdir is None:
        yield
        return
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
