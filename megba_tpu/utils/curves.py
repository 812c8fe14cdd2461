"""Capture and parse the LM solver's verbose per-iteration lines.

Parses the per-iteration `iter k: cost ...` line
(observability/emit.py:_emit_verbose_line — the reference's observable,
lm_algo.cu:149-162) back into a cost curve.  A format drift raises
here instead of silently producing an empty curve.
"""

from __future__ import annotations

import contextlib
import io
import re
from typing import Callable, Optional

# The cost group matches nan/inf too: a diverged run's iterations must
# stay visible in the parsed curve instead of vanishing — an all-nan
# solve previously looked like a verbose format drift rather than the
# divergence it was.
_LINE = re.compile(
    r"iter (\d+): cost (-?(?:[0-9.eE+-]+|nan|inf)) .*accept (True|False) "
    r"pcg_iters (\d+)")


def parse_verbose_curve(text: str, require: bool = True) -> list[dict]:
    """Verbose solver stdout -> [{iter, cost, accept, pcg_iters}, ...]."""
    curve = [
        {"iter": int(m.group(1)), "cost": float(m.group(2)),
         "accept": m.group(3) == "True", "pcg_iters": int(m.group(4))}
        for m in _LINE.finditer(text)]
    if require and not curve:
        raise ValueError(
            "no verbose iteration lines matched — did the solver's "
            "verbose format (observability/emit.py:_emit_verbose_line) "
            "change without updating utils/curves._LINE?")
    return curve


class _Tee(io.TextIOBase):
    """Buffer that also passes writes through to a live stream."""

    def __init__(self, passthrough):
        self.buf = io.StringIO()
        self._live = passthrough

    def write(self, s):
        self.buf.write(s)
        self._live.write(s)
        return len(s)

    def flush(self):
        self._live.flush()


def run_with_curve(fn: Callable[[], object],
                   block_on: Optional[Callable[[object], object]] = None,
                   tee: bool = False):
    """Run `fn` capturing stdout; return (result, curve).

    `block_on(result)` (default: jax.block_until_ready on the result)
    runs INSIDE the capture so asynchronously-emitted verbose callbacks
    have flushed before parsing.  `tee=True` additionally passes every
    line through to the real stdout as it is emitted — use it for
    long runs so a crash mid-solve still leaves the per-iteration
    forensics in the log instead of dying inside the buffer.
    """
    import sys

    import jax

    buf = _Tee(sys.stdout) if tee else io.StringIO()
    with contextlib.redirect_stdout(buf):
        result = fn()
        if block_on is None:
            jax.block_until_ready(result)
        else:
            block_on(result)
    text = buf.buf.getvalue() if tee else buf.getvalue()
    return result, parse_verbose_curve(text)
