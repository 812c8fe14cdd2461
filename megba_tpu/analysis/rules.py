"""The lint rules.  Each rule is a generator over a PackageIndex.

Rule ids are kebab-case; suppress one finding with an inline
`# megba: allow-<rule>` pragma on the flagged physical line.

| id | contract it enforces |
|---|---|
| host-callback | `jax.debug.callback` / `jax.debug.print` / `io_callback` / `pure_callback` only inside the designated host-interop modules (observability/, utils/debug.py) — anywhere else a callback silently punches a host round-trip into the fused device program |
| np-in-jit | no `np.*` calls, `float(...)` or `.item()` coercions in functions reachable from a jitted entry point — each is either a trace-time constant bake (silent retrace per value) or a ConcretizationError waiting for the first non-static input |
| implicit-dtype | `jnp.zeros/ones/empty/full/arange/eye/linspace/identity` must state a dtype (keyword or the documented positional slot); `jnp.array`/`jnp.asarray` of pure Python literals too — the f32 default silently breaks f64/f32 parity |
| scalar-promotion | no strongly-typed scalar constructors (`np.float64(x)`, `jnp.int32(k)`, ...) as operands of array arithmetic in jit-reachable code — unlike weak Python scalars they promote the whole expression's dtype |
| donated-reuse | an argument passed at a `donate_argnums` position of a locally-built `jax.jit` program must not be read after the call — the buffer is deleted by the call |
| weak-literal | no BARE float literal as a `jnp.where` branch or `jnp.clip` bound in jit-reachable code — probed on this jaxlib: under x64 those positions materialise a `tensor<f64>` constant (plus a convert) in f32 programs, the dtype-census leak hand-fixed in PRs 3 and 6 (`jnp.where(safe, θ², 1.0)`, `jnp.where(..., 0.0, ...)`); use `zeros_like`/`ones_like`/`jnp.asarray(c, x.dtype)`.  Plain arithmetic (`2.0 * x`) and `jnp.maximum/minimum` literals promote weakly and are clean — the rule matches only the probed leaky positions |
| raw-clock | no raw `time.time()` / `time.perf_counter()` outside the sanctioned clock homes (`utils/timing.py`, `observability/`) — scattered raw reads fragment the timing story the observability plane narrates (PhaseTimer phases, span timestamps, report `created_unix` all flow from ONE seam); use `utils.timing.monotonic_s()` for durations and `utils.timing.wall_unix()` for epoch stamps.  `time.monotonic()` deadline arithmetic and `time.sleep` are clean — the rule bans the two reads that LOOK interchangeable but are not.  STRICT lane (`serving/transport.py`, `robustness/netfaults.py`): `time.monotonic()` is banned there too — transport deadlines ride `monotonic_s()` exclusively, and a second monotonic epoch would be compared against it |
| guarded-by | shared mutable attributes of lock-owning classes, declared with `# megba: guarded-by(<lockattr>)` on the assignment (or inferred at >= 80% locked accesses in thread-reachable classes), must not be read/written outside a `with <lock>` block — the host serving tier's race detector (analysis/concurrency.py); `# megba: allow-unguarded` is the per-line escape hatch |
| lock-order | the package-wide acquires-while-holding digraph (nested `with` blocks, cross-method/cross-class edges through the callgraph, `Condition.wait` re-acquires) must be acyclic — a cycle is a deadlock waiting for the right interleaving; the finding prints the witness path |
| stale-program | every option field READ on the lowering closure (flat_solve / distributed_lm_solve / batched_solve_program / lower_bucket / solve_pgo and everything they reach) must be visible to the program's static key — a strip-listed or key-exempt-declared field read under tracing is a wrong-program hazard, and a builder whose `static_key(...)` omits its option parameter hides every field (analysis/identity.py); consume-and-strip in the same function is the sanctioned shape |
| cache-split | an option field that reaches the key surfaces (static_key reprs the whole frozen option; artifact fingerprints, warm manifests and bucket keys follow) but is never lowering-read and is not on the observability strip-list silently fragments every cache — declare intent with a field-scoped lowering-relevant pragma (program-family selectors) or key-exempt pragma (true host-only knobs) on the declaration line |
| key-surface-drift | the strip-list is ONE registry (common.OBSERVABILITY_FIELDS): partial strips, non-conforming strip helpers, hardcoded membership tuples that disagree with it, un-stripped memoised-cache fronts, contradictory/unknown-field pragmas, and operand-declared values branched on in Python inside traced code (operand-as-static; `is None` presence checks sanctioned) all drift a key surface away from the contract |
| blocking-under-lock | no call from the curated blocking set (`Future.result`, `queue.get`/`join`, socket/pipe `recv*`, `subprocess`-style `.wait`, `time.sleep` above 0.05 s, the RPC `_recv_frame`) while any lock is held — the classic serve-loop stall shape; waiting on a HELD Condition is the sanctioned exception (it releases the lock) |
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, Iterator, List, Optional, Set, Tuple

from megba_tpu.analysis import concurrency
from megba_tpu.analysis.callgraph import (
    FunctionInfo,
    ModuleInfo,
    PackageIndex,
    _dotted,
)

# Modules allowed to host callbacks / host coercions: the designated
# host-interop layer.  Matched on dotted module-name suffixes so the
# linter works from any invocation directory.
HOST_INTEROP_MODULES = (
    "observability",
    "utils.debug",
)

_CALLBACK_TAILS = {"io_callback", "pure_callback"}
_CALLBACK_DOTTED_TAILS = ("debug.callback", "debug.print")

_NUMPY_HEADS = {"numpy"}
_JNP_HEADS = {"jax.numpy"}

# constructor name -> positional index where dtype may legally appear
# (None: keyword-only in practice for this repo's call shapes)
_DTYPE_SLOT = {
    "zeros": 1, "ones": 1, "empty": 1, "array": 1, "asarray": 1,
    "full": 2, "arange": 3, "eye": 3, "identity": 1, "linspace": None,
}

_SCALAR_CTORS = {
    "float16", "float32", "float64", "bfloat16",
    "int8", "int16", "int32", "int64",
    "uint8", "uint16", "uint32", "uint64", "bool_",
}

ALL_RULES = (
    "host-callback",
    "np-in-jit",
    "implicit-dtype",
    "scalar-promotion",
    "donated-reuse",
    "weak-literal",
    "raw-clock",
    "guarded-by",
    "lock-order",
    "blocking-under-lock",
    "stale-program",
    "cache-split",
    "key-surface-drift",
)

# Fully-resolved call targets the raw-clock rule bans (time.monotonic,
# time.sleep etc. stay legal — only the two reads that masquerade as
# each other are fenced into the clock homes).
_RAW_CLOCK_TARGETS = {"time.time", "time.perf_counter"}

# Modules on the STRICT clock lane: deadline arithmetic here rides
# `utils.timing.monotonic_s` exclusively, so even `time.monotonic()` is
# banned — a second monotonic epoch in the transport/chaos layer would
# let a deadline computed on one clock be compared against the other
# (they share no epoch, only a rate).
_STRICT_CLOCK_MODULES = ("serving.transport", "robustness.netfaults")
_STRICT_CLOCK_TARGETS = _RAW_CLOCK_TARGETS | {"time.monotonic"}


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    rule: str
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


def _is_host_interop(mod: ModuleInfo) -> bool:
    parts = mod.name.split(".")
    return "observability" in parts or mod.name.endswith("utils.debug")


def _alias_target(mod: ModuleInfo, dotted: Optional[str]) -> Optional[str]:
    """Resolve the head alias of a dotted chain through the module's
    imports: "np.zeros" -> "numpy.zeros", "jnp.array" -> "jax.numpy.array"."""
    if dotted is None:
        return None
    head, *rest = dotted.split(".")
    target = mod.imports.get(head, head)
    return ".".join([target] + rest)


def _own_nodes(info: FunctionInfo) -> Iterator[ast.AST]:
    """Walk a function's own body, not descending into nested defs
    (those are indexed and checked as functions in their own right)."""
    stack: List[ast.AST] = list(ast.iter_child_nodes(info.node))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


# --------------------------------------------------------------- rules

def rule_host_callback(index: PackageIndex) -> Iterator[Finding]:
    for mod in index.modules.values():
        if _is_host_interop(mod):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            if dotted is None:
                continue
            full = _alias_target(mod, dotted)
            tail = dotted.split(".")[-1]
            hit = tail in _CALLBACK_TAILS or any(
                dotted.endswith(t) or (full or "").endswith(t)
                for t in _CALLBACK_DOTTED_TAILS)
            if hit:
                yield Finding(
                    mod.path, node.lineno, node.col_offset, "host-callback",
                    f"`{dotted}` outside the host-interop layer "
                    "(observability/, utils/debug.py): callbacks break the "
                    "single-fused-program contract; route host output "
                    "through observability/emit.py")


def rule_np_in_jit(index: PackageIndex) -> Iterator[Finding]:
    for qual in sorted(index.reachable):
        info = index.functions[qual]
        mod = index.modules[info.module]
        if _is_host_interop(mod):
            continue
        for node in _own_nodes(info):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            full = _alias_target(mod, dotted)
            if full is not None and full.split(".")[0] in _NUMPY_HEADS:
                yield Finding(
                    mod.path, node.lineno, node.col_offset, "np-in-jit",
                    f"host numpy call `{dotted}` inside jit-reachable "
                    f"`{qual.split('.')[-1]}`: it runs at trace time and "
                    "bakes a constant (or retraces per value); use jnp")
            elif (isinstance(node.func, ast.Name)
                  and node.func.id == "float" and node.args):
                yield Finding(
                    mod.path, node.lineno, node.col_offset, "np-in-jit",
                    "`float(...)` inside jit-reachable "
                    f"`{qual.split('.')[-1]}`: concretizes a traced value "
                    "(ConcretizationError on non-static input)")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "item" and not node.args):
                yield Finding(
                    mod.path, node.lineno, node.col_offset, "np-in-jit",
                    "`.item()` inside jit-reachable "
                    f"`{qual.split('.')[-1]}`: host sync/concretization in "
                    "traced code")


def _literal_only(node: ast.AST) -> bool:
    """True when the expression tree is pure Python literals (the cases
    where jnp.array has no operand dtype to inherit)."""
    if isinstance(node, ast.Constant):
        return not isinstance(node.value, (str, bytes, type(None)))
    if isinstance(node, (ast.List, ast.Tuple)):
        return all(_literal_only(e) for e in node.elts)
    if isinstance(node, ast.UnaryOp) and isinstance(
            node.op, (ast.USub, ast.UAdd)):
        return _literal_only(node.operand)
    return False


def rule_implicit_dtype(index: PackageIndex) -> Iterator[Finding]:
    for mod in index.modules.values():
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            full = _alias_target(mod, dotted)
            if full is None:
                continue
            head, _, tail = full.rpartition(".")
            if head not in _JNP_HEADS or tail not in _DTYPE_SLOT:
                continue
            if any(kw.arg == "dtype" for kw in node.keywords):
                continue
            slot = _DTYPE_SLOT[tail]
            if slot is not None and len(node.args) > slot:
                continue  # positional dtype present
            if tail in ("array", "asarray"):
                if not (node.args and _literal_only(node.args[0])):
                    continue  # inherits dtype from its operands
            yield Finding(
                mod.path, node.lineno, node.col_offset, "implicit-dtype",
                f"`jnp.{tail}` without an explicit dtype defaults to "
                "float32/weak: state the dtype (problem dtype, operand "
                ".dtype, or jnp.int32 for indices) so f64 runs stay f64")


def rule_scalar_promotion(index: PackageIndex) -> Iterator[Finding]:
    for qual in sorted(index.reachable):
        info = index.functions[qual]
        mod = index.modules[info.module]
        for node in _own_nodes(info):
            if not isinstance(node, ast.BinOp):
                continue
            for side in (node.left, node.right):
                if not isinstance(side, ast.Call):
                    continue
                full = _alias_target(mod, _dotted(side.func)) or ""
                head, _, tail = full.rpartition(".")
                if (head in _NUMPY_HEADS | _JNP_HEADS
                        and tail in _SCALAR_CTORS):
                    yield Finding(
                        mod.path, node.lineno, node.col_offset,
                        "scalar-promotion",
                        f"strongly-typed scalar `{_dotted(side.func)}` in "
                        "array arithmetic promotes the whole expression's "
                        "dtype (weak Python scalars would not); cast with "
                        "jnp.asarray(x, arr.dtype) instead")


def _float_literal(node: ast.AST) -> bool:
    """A bare Python float literal (optionally signed) — the weak
    scalar that materialises as a wide constant in the leaky call
    positions."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, float)
    if isinstance(node, ast.UnaryOp) and isinstance(
            node.op, (ast.USub, ast.UAdd)):
        return _float_literal(node.operand)
    return False


# call tail -> the positional argument slots whose bare float literals
# leak (jnp.where branches; jnp.clip bounds) + the keyword spellings of
# the same slots.
_WEAK_LITERAL_SLOTS = {
    "where": ((1, 2), ("x", "y")),
    "clip": ((1, 2), ("a_min", "a_max", "min", "max")),
}


def rule_weak_literal(index: PackageIndex) -> Iterator[Finding]:
    # ALL functions, not just the jit-reachable set: the leak class was
    # found in the ANALYTICAL Jacobian chain (ops/geo.py), which is
    # jitted through an engine reference the call graph cannot follow —
    # exactly the blind spot that let it survive PR 3's census fixes.
    for qual, info in sorted(index.functions.items()):
        mod = index.modules[info.module]
        for node in _own_nodes(info):
            if not isinstance(node, ast.Call):
                continue
            full = _alias_target(mod, _dotted(node.func)) or ""
            head, _, tail = full.rpartition(".")
            if head not in _JNP_HEADS or tail not in _WEAK_LITERAL_SLOTS:
                continue
            slots, kwnames = _WEAK_LITERAL_SLOTS[tail]
            hits = [node.args[p] for p in slots if p < len(node.args)
                    and _float_literal(node.args[p])]
            hits += [kw.value for kw in node.keywords
                     if kw.arg in kwnames and _float_literal(kw.value)]
            for h in hits:
                yield Finding(
                    mod.path, h.lineno, h.col_offset, "weak-literal",
                    f"bare float literal as a `jnp.{tail}` "
                    f"{'branch' if tail == 'where' else 'bound'} "
                    "materialises a wide (f64-under-x64) constant "
                    "tensor in f32 programs (dtype-census leak); use "
                    "zeros_like/ones_like or jnp.asarray(c, x.dtype)")


def _is_clock_home(mod: ModuleInfo) -> bool:
    parts = mod.name.split(".")
    return "observability" in parts or mod.name.endswith("utils.timing")


def rule_raw_clock(index: PackageIndex) -> Iterator[Finding]:
    for mod in index.modules.values():
        if _is_clock_home(mod):
            continue
        strict = mod.name.endswith(_STRICT_CLOCK_MODULES)
        targets = _STRICT_CLOCK_TARGETS if strict else _RAW_CLOCK_TARGETS
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            full = _alias_target(mod, dotted)
            if full in targets:
                helper = ("wall_unix()" if full == "time.time"
                          else "monotonic_s()")
                if strict and full == "time.monotonic":
                    yield Finding(
                        mod.path, node.lineno, node.col_offset,
                        "raw-clock",
                        f"raw `{dotted}()` in a strict-clock module "
                        "(transport/netfaults deadline arithmetic): use "
                        "megba_tpu.utils.timing.monotonic_s() — a "
                        "second monotonic epoch here would be compared "
                        "against monotonic_s() deadlines")
                else:
                    yield Finding(
                        mod.path, node.lineno, node.col_offset,
                        "raw-clock",
                        f"raw `{dotted}()` outside the clock homes "
                        "(utils/timing.py, observability/): use "
                        f"megba_tpu.utils.timing.{helper} so durations "
                        "and epoch stamps flow from one seam")


def rule_donated_reuse(index: PackageIndex) -> Iterator[Finding]:
    for qual, info in sorted(index.functions.items()):
        mod = index.modules[info.module]
        yield from _donated_reuse_in(mod, info)


def _donated_reuse_in(mod: ModuleInfo,
                      info: FunctionInfo) -> Iterator[Finding]:
    donated_fns: Dict[str, Tuple[int, ...]] = {}
    # (var name tainted, donating call first line, call last line)
    taints: List[Tuple[str, int, int]] = []

    nodes = sorted(
        (n for n in _own_nodes(info)),
        key=lambda n: (getattr(n, "lineno", 0), getattr(n, "col_offset", 0)))

    for node in nodes:
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            dotted = _dotted(node.value.func) or ""
            if dotted.split(".")[-1] == "jit":
                positions = _donate_positions(node.value)
                if positions:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            donated_fns[tgt.id] = positions
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            positions = donated_fns.get(node.func.id)
            if positions:
                for p in positions:
                    if p < len(node.args) and isinstance(
                            node.args[p], ast.Name):
                        # Taint from the call's LAST line: a wrapped
                        # call's own arguments on continuation lines are
                        # not reads-after-donation.
                        taints.append((
                            node.args[p].id, node.lineno,
                            getattr(node, "end_lineno", node.lineno)
                            or node.lineno))

    if not taints:
        return
    # Any Load of a tainted name strictly after its donating call (and
    # before a rebinding Store) is a use of a deleted buffer.
    events: Dict[str, List[Tuple[int, int, str, ast.AST]]] = {}
    for node in _own_nodes(info):
        if isinstance(node, ast.Name):
            kind = "load" if isinstance(node.ctx, ast.Load) else "store"
            events.setdefault(node.id, []).append(
                (node.lineno, node.col_offset, kind, node))
    for name, call_line, call_end in taints:
        for lineno, col, kind, node in sorted(events.get(name, [])):
            if lineno < call_line:
                continue
            if lineno <= call_end:
                if kind == "store":
                    break  # `x = prog(x, ...)`: rebound to the result
                continue  # the donating call's own argument load
            if kind == "store":
                break  # rebound: taint ends
            yield Finding(
                mod.path, lineno, col, "donated-reuse",
                f"`{name}` was donated to a jitted call on line "
                f"{call_line} (its device buffer is deleted by the call); "
                "reading it afterwards raises 'Array has been deleted'")
            break  # one finding per taint is enough


def _donate_positions(call: ast.Call) -> Tuple[int, ...]:
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                out = []
                for e in v.elts:
                    if isinstance(e, ast.Constant) and isinstance(
                            e.value, int):
                        out.append(e.value)
                return tuple(out)
    return ()


# ------------------------------------------------ concurrency rules
# The analysis lives in analysis/concurrency.py (it yields plain
# (path, line, col, message) tuples so it never needs this module);
# these wrappers stamp the rule ids.


def rule_guarded_by(index: PackageIndex) -> Iterator[Finding]:
    for path, line, col, msg in concurrency.find_guarded_by(index):
        yield Finding(path, line, col, "guarded-by", msg)


def rule_lock_order(index: PackageIndex) -> Iterator[Finding]:
    for path, line, col, msg in concurrency.find_lock_order(index):
        yield Finding(path, line, col, "lock-order", msg)


def rule_blocking_under_lock(index: PackageIndex) -> Iterator[Finding]:
    for path, line, col, msg in concurrency.find_blocking_under_lock(index):
        yield Finding(path, line, col, "blocking-under-lock", msg)


# -------------------------------------------- program-identity rules
# The analysis lives in analysis/identity.py (same contract as the
# concurrency lane: plain (path, line, col, message) tuples, memoised
# on the index); these wrappers stamp the rule ids.


def rule_stale_program(index: PackageIndex) -> Iterator[Finding]:
    from megba_tpu.analysis import identity

    for path, line, col, msg in identity.find_stale_program(index):
        yield Finding(path, line, col, "stale-program", msg)


def rule_cache_split(index: PackageIndex) -> Iterator[Finding]:
    from megba_tpu.analysis import identity

    for path, line, col, msg in identity.find_cache_split(index):
        yield Finding(path, line, col, "cache-split", msg)


def rule_key_surface_drift(index: PackageIndex) -> Iterator[Finding]:
    from megba_tpu.analysis import identity

    for path, line, col, msg in identity.find_key_surface_drift(index):
        yield Finding(path, line, col, "key-surface-drift", msg)


RULES = {
    "host-callback": rule_host_callback,
    "np-in-jit": rule_np_in_jit,
    "implicit-dtype": rule_implicit_dtype,
    "scalar-promotion": rule_scalar_promotion,
    "donated-reuse": rule_donated_reuse,
    "weak-literal": rule_weak_literal,
    "raw-clock": rule_raw_clock,
    "guarded-by": rule_guarded_by,
    "lock-order": rule_lock_order,
    "blocking-under-lock": rule_blocking_under_lock,
    "stale-program": rule_stale_program,
    "cache-split": rule_cache_split,
    "key-surface-drift": rule_key_surface_drift,
}
