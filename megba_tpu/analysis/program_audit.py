"""Compiled-program auditor: static CI gates on the lowered executables.

PR 2's AST linter sees Python source; this layer sees what XLA actually
emitted.  Each canonical solver program — the single-device `flat_solve`
program, its tiled variant, the sharded SPMD program from
`parallel/mesh.py`, and the PGO program (single + sharded) — is
AOT-lowered on small synthetic problems via the production entry points
themselves (`flat_solve(..., lower_only=True)` / `solve_pgo(...,
lower_only=True)`: same host prep, same jit caches, same donation
flags), compiled, and audited in four passes:

1. **transfer-freedom** — walk the StableHLO for host callbacks /
   infeed / outfeed / send / recv custom_calls; any occurrence outside
   the observability-sanctioned targets fails (MegBA's contract: one
   fused device program per solve, zero host round-trips — arxiv
   2112.01349 §4).
2. **collective census** — enumerate all-reduce / all-gather /
   collective-permute ops in the *optimized* HLO (post-DCE truth),
   attribute them to program regions via the `jax.named_scope` paths in
   op metadata, and compare against the analytic per-PCG-iteration
   expectation: exactly TWO reductions inside the PCG while body for
   the Schur solve (hlp + hpl per S·p product), ONE for PGO's
   matrix-free H·x.  An accidental extra sync is a lint failure with
   the offending op named.
3. **dtype census + donation** — no f64 tensor in an f32 solve (and
   vice versa; weak Python literals that materialise as wide constants
   count), and every declared donation must have materialised as
   input-output aliasing in the compiled executable.
4. **budget gate** — `cost_analysis()` FLOPs / bytes-accessed and
   `memory_analysis()` peak temp size against the committed
   `ANALYSIS_BUDGET.json` (analysis/budget.py): >15% drift fails,
   collective-count changes fail exactly.

The CLI lives in `python -m megba_tpu.analysis.audit` (--check /
--update); scripts/lint.sh runs it as gate 4.  Everything is
CPU-lowered: passes run without executing a single solver FLOP.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from megba_tpu.analysis import hlo

# Scope-path fragment that marks the PCG inner loop's body in compiled
# op metadata (jax.named_scope "megba.pcg_core" + the while lowering).
PCG_BODY_MARK = "megba.pcg_core/while/body"


def pcg_body_collective_summary(
    compiled_ops: Sequence[hlo.HloOp], world: int,
) -> Tuple[List[hlo.HloOp], Dict[str, int], float]:
    """PCG-body collectives of a compiled program: (ops, kind -> count
    census, ring-model bytes moved per device per CG step).

    The body-mark filter + byte model behind the ProgramAudit.pcg_body_*
    passes and the budget gate's `collective_bytes_per_sp` axis."""
    body = [op for op in hlo.collective_ops(compiled_ops)
            if op.op_name and PCG_BODY_MARK in op.op_name]
    census: Dict[str, int] = {}
    for op in body:
        census[op.kind] = census.get(op.kind, 0) + 1
    bytes_moved = float(sum(
        hlo.collective_bytes_moved(op, world) for op in body))
    return body, census, bytes_moved

# custom_call targets the observability layer is allowed to emit (the
# sanctioned trace outputs).  The canonical audited programs are built
# verbose=False so none should appear at all, but the allowance keeps
# the pass honest if a sanctioned trace output ever becomes part of a
# canonical program.
SANCTIONED_TRANSFER_TARGETS: Tuple[str, ...] = ()

_WRONG_FAMILY = {
    "f32": ("f64", "bf16", "f16"),
    "f64": ("f32", "bf16", "f16"),
}

# StableHLO op kinds a declared bf16 surface may carry bf16 tensors in:
# storage/movement ops (converts, gathers, slices, layout shuffles),
# the bf16 multiply itself, and dot_general (whose RESULT must still be
# f32 — checked separately).  Collective kinds are allowed only when
# the surface declares `collectives=True`.  Accumulation kinds (add /
# subtract / reduce) are NEVER allowed on non-scalar bf16 tensors —
# f32 accumulation is the contract — except the rank-0 adds inside a
# declared collective's reduction region (the wire-payload sum the
# collective gate explicitly buys).
BF16_ALLOWED_KINDS: Tuple[str, ...] = (
    "convert", "multiply", "dot_general",
    "gather", "dynamic_slice", "slice", "dynamic_update_slice",
    "reshape", "transpose", "broadcast_in_dim", "concatenate",
    "select", "pad", "constant", "optimization_barrier", "return",
    "custom_call",
    # jax's while lowering threads closure arrays (the bf16 coupling
    # rows / M⁻¹ copy) through the loop as invariant carries, so the
    # while op's own signature legitimately names bf16 tensors.
    "while",
)

_BF16_ACCUM_KINDS = frozenset({"add", "subtract", "reduce", "dot_general"})


@dataclasses.dataclass(frozen=True)
class Bf16Surface:
    """The DECLARED bf16 surface of one canonical program.

    A program spec carrying one of these opts into the bf16 audit pass
    (`ProgramAudit.bf16_surface_violations`) instead of the blanket
    "bf16 is a wrong-family dtype" rule:

    - every StableHLO op touching a bf16 tensor must be of an
      `allowed_kinds` kind (collective kinds additionally need
      `collectives=True`) — a bf16 op leaking outside the declared
      surface fails the audit naming the op;
    - NO accumulation may produce a bf16 result: an add/subtract/
      reduce with a non-scalar bf16 result, or a dot_general whose
      result is bf16 (preferred_element_type dropped), is exactly the
      "accumulation not f32" regression this pass exists to catch.
      Rank-0 bf16 adds are the reduction regions of the declared
      collectives and are allowed iff `collectives=True`;
    - converts may only cross between bf16 and f32 — a bf16<->f64
      convert is a family leak;
    - at least `min_compute_ops` bf16 multiplies / bf16-operand
      dot_generals must EXIST: a refactor that silently upcasts the
      operands before every product (the compiler or a well-meaning
      edit) leaves a program that still carries bf16 tensors but runs
      f32 math — the win evaporates while the census stays green, so
      its absence is a violation, not a shrug.
    """

    allowed_kinds: Tuple[str, ...] = BF16_ALLOWED_KINDS
    collectives: bool = False
    min_compute_ops: int = 1


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One canonical program: how to lower it + its audited invariants."""

    name: str
    float_family: str  # "f32" | "f64" — every float tensor must be this
    world: int  # mesh size; 1 => no collectives allowed at all
    pcg_psums: int  # all-reduces expected inside the PCG while body
    donate_leaves: Tuple[int, ...]  # flat params declared donated
    build: Callable[[], object]  # () -> jax.stages.Lowered
    # Collective kinds this program may emit anywhere (psum lowers to
    # all-reduce; the 2-D mesh programs additionally carry the
    # subgroup-stage kinds).  Any other kind is a violation.
    allowed_kinds: Tuple[str, ...] = ("all_reduce",)
    # Exact kind -> count census of the PCG while BODY (one CG step),
    # as (kind, count) pairs.  None = only the all-reduce count above
    # is pinned (the historical 1-D contract, byte-identical).
    pcg_body_census: Optional[Tuple[Tuple[str, int], ...]] = None
    # When True, every collective inside the PCG body must be
    # SUBGROUP-scoped: its replica groups (permute: its ring cycles)
    # span strictly fewer than `world` devices.  The 2-D mesh's whole
    # point — a world-wide reduce sneaking back into the body is
    # exactly the regression this pins against.
    pcg_subgroup_only: bool = False
    # Declared bf16 surface (None = any bf16 occurrence is a
    # wrong-family dtype leak, the historical rule).  With
    # `collectives=True` the PCG-body byte model additionally prices
    # the DECLARED (StableHLO) payload dtype instead of the compiled
    # one: probed on this jaxlib (0.4.36, XLA:CPU), the CPU backend's
    # float-normalization pass promotes bf16 collectives back to f32
    # in the compiled executable, so the CPU audit lane would price
    # wire bytes the program never asked to move — a TPU lowering
    # (native bf16 collectives) moves the declared payload, and the
    # surface pass pins that the declaration exists.
    bf16_surface: Optional[Bf16Surface] = None
    # Declared analytical per-S·p budget (analysis/edge_budget.py):
    # (metric, value) pairs — `flops_per_sp` / `bytes_touched_per_sp` —
    # priced from the problem geometry, the edge-stream plan (padding
    # included) and the dtype surface, with zero compiler in the loop.
    # Exact-gated in ANALYSIS_BUDGET.json: the committed number pins
    # the INPUTS, so a plan change, a quantum bump, or a dtype-surface
    # edit fails `--check` naming the program.  Spec-carried, not
    # measured, so the axes survive a backend without cost analysis.
    sp_budget: Optional[Tuple[Tuple[str, float], ...]] = None


@dataclasses.dataclass
class ProgramAudit:
    """Artifacts + derived census of one lowered/compiled program."""

    spec: ProgramSpec
    stablehlo: str
    compiled_text: str
    flops: float
    bytes_accessed: float
    peak_temp_bytes: float
    argument_bytes: float
    output_bytes: float
    # Pre-optimization HLO with op_name metadata: the collectives as the
    # source wrote them, before XLA's all-reduce combiner merges
    # neighbours into one tuple op that keeps only one op's scope.
    source_hlo: str = ""

    @functools.cached_property
    def stablehlo_ops(self) -> List[hlo.HloOp]:
        return hlo.parse_stablehlo_ops(self.stablehlo)

    @functools.cached_property
    def source_collectives(self) -> List[hlo.HloOp]:
        return hlo.collective_ops(hlo.parse_compiled_ops(self.source_hlo))

    @functools.cached_property
    def compiled_ops(self) -> List[hlo.HloOp]:
        return hlo.parse_compiled_ops(self.compiled_text)

    @functools.cached_property
    def collectives(self) -> List[hlo.HloOp]:
        return hlo.collective_ops(self.compiled_ops)

    @functools.cached_property
    def declared_collective_payloads(self) -> List[hlo.CollectivePayload]:
        """StableHLO-declared collective payloads, parsed once (the
        byte repricing and the bf16 surface pass both read them)."""
        return hlo.stablehlo_collective_payloads(self.stablehlo)

    # ---- pass 1: transfer freedom ------------------------------------
    def transfer_violations(self) -> List[str]:
        bad = hlo.transfer_ops(self.stablehlo_ops,
                               allow=SANCTIONED_TRANSFER_TARGETS)
        return [
            f"{self.spec.name}: host transfer in compiled program — "
            f"{op.where()} :: {op.text[:120]}"
            for op in bad
        ]

    # ---- pass 2: collective census -----------------------------------
    @functools.cached_property
    def _pcg_body_summary(self) -> Tuple[
            List[hlo.HloOp], Dict[str, int], float]:
        body, census, bytes_moved = pcg_body_collective_summary(
            self.compiled_ops, self.spec.world)
        surf = self.spec.bf16_surface
        if surf is not None and surf.collectives and body:
            repriced = self._declared_payload_bytes(body)
            if repriced is not None:
                bytes_moved = repriced
        return body, census, bytes_moved

    def _declared_payload_bytes(self, body) -> Optional[float]:
        """Ring-model PCG-body bytes priced at the DECLARED (StableHLO)
        payload dtype, replica-group structure from the compiled op.

        Pairs every compiled in-body collective with a StableHLO
        collective at while depth >= 2 (the PCG while body — the LM
        loop is depth 1) by (kind, element count).  Returns None when
        the pairing is incomplete — `bf16_surface_violations` raises
        that as an explicit violation, so the byte axis can never
        silently fall back to a mis-priced payload.  Why this exists:
        XLA:CPU's float normalization promotes bf16 collectives to f32
        in the compiled executable (probed — see ProgramSpec), so the
        compiled dtype on the audit lane is not the payload a bf16-
        capable backend moves.
        """
        declared = [op for op in self.declared_collective_payloads
                    if op.while_depth >= 2]
        pool: Dict[Tuple[str, Optional[int]], list] = {}
        for op in declared:
            pool.setdefault((op.kind, op.result_elems), []).append(op)
        total = 0.0
        for cop in body:
            cand = pool.get((cop.kind, cop.result_elems))
            if not cand:
                return None
            dop = cand.pop()
            b = (float(cop.result_elems or 0)
                 * hlo.DTYPE_BYTES.get(dop.result_dtype or "", 0))
            total += hlo.collective_bytes_moved(
                dataclasses.replace(cop, result_bytes=b,
                                    result_dtype=dop.result_dtype),
                self.spec.world)
        return total

    def pcg_body_collectives(self) -> List[hlo.HloOp]:
        return self._pcg_body_summary[0]

    def pcg_body_kind_census(self) -> Dict[str, int]:
        """kind -> count of the collectives inside the PCG while body."""
        return self._pcg_body_summary[1]

    def pcg_body_collective_bytes(self) -> float:
        """Ring-model bytes moved per device per CG step: the sum of
        `hlo.collective_bytes_moved` over the PCG body's collectives —
        the budget gate's `collective_bytes_per_sp` axis."""
        return self._pcg_body_summary[2]

    def collective_violations(self) -> List[str]:
        out: List[str] = []
        if self.spec.world == 1:
            for op in self.collectives:
                out.append(
                    f"{self.spec.name}: collective in a single-device "
                    f"program — {op.where()}")
            return out
        allowed = frozenset(self.spec.allowed_kinds)
        bad_kind = [op for op in self.collectives if op.kind not in allowed]
        for op in bad_kind:
            out.append(
                f"{self.spec.name}: unexpected collective kind "
                f"(allowed: {sorted(allowed)}) — {op.where()}")
        pcg = self.pcg_body_collectives()
        n_ar = sum(1 for op in pcg if op.kind == "all_reduce")
        # Single source of truth for the all-reduce expectation: the
        # full kind census when the spec pins one (the 2-D program),
        # the scalar pcg_psums otherwise — never two hand-synced pins.
        want_ar = (dict(self.spec.pcg_body_census).get("all_reduce", 0)
                   if self.spec.pcg_body_census is not None
                   else self.spec.pcg_psums)
        if n_ar != want_ar:
            ops = "\n".join(f"    {op.where()}" for op in pcg) or "    (none)"
            out.append(
                f"{self.spec.name}: {n_ar} all-reduce(s) inside the "
                f"PCG while body, analytic expectation is "
                f"{want_ar} per CG step "
                f"(MegBA per-iteration collective pattern):\n{ops}")
        if self.spec.pcg_body_census is not None:
            want = dict(self.spec.pcg_body_census)
            got = self.pcg_body_kind_census()
            if got != want:
                out.append(
                    f"{self.spec.name}: PCG-body collective census "
                    f"{got} != pinned expectation {want} — the "
                    "per-iteration communication pattern changed")
        if self.spec.pcg_subgroup_only:
            for op in pcg:
                g = op.group_size(self.spec.world)
                if g is None:
                    out.append(
                        f"{self.spec.name}: PCG-body collective carries "
                        f"no parseable replica groups (cannot certify "
                        f"subgroup scope) — {op.where()}")
                elif g >= self.spec.world:
                    out.append(
                        f"{self.spec.name}: WORLD-spanning collective "
                        f"(group size {g} of world {self.spec.world}) "
                        f"inside the PCG body — the 2-D mesh contract "
                        f"is subgroup-scoped stages — {op.where()}")
        return out

    # ---- pass 3: dtype census + donation + bf16 surface --------------
    def dtype_violations(self) -> List[str]:
        census = hlo.dtype_census(self.stablehlo)
        out: List[str] = []
        wrongs = _WRONG_FAMILY[self.spec.float_family]
        if self.spec.bf16_surface is not None:
            # bf16 is the declared surface, not a leak; f64/f16 stay
            # wrong, and the surface pass polices WHERE bf16 appears.
            wrongs = tuple(w for w in wrongs if w != "bf16")
        for wrong in wrongs:
            n = census.get(wrong, 0)
            if not n:
                continue
            sites = hlo.lines_with_dtype(self.stablehlo, wrong, limit=3)
            where = "\n".join(f"    line {ln}: {txt[:140]}"
                              for ln, txt in sites)
            out.append(
                f"{self.spec.name}: {n} {wrong} tensor occurrence(s) in "
                f"a {self.spec.float_family} solve (dtype leak):\n{where}")
        return out

    def bf16_surface_violations(self) -> List[str]:
        """The allowed-bf16-surface pass (specs with `bf16_surface`).

        Without a declared surface this pass is empty — any bf16 then
        already fails the wrong-family census above.  With one, four
        contracts are enforced (Bf16Surface docstring): kind
        allow-list, f32 accumulation, converts confined to bf16<->f32,
        and the presence of actual bf16 compute (the silent-upcast
        guard).  Under `collectives=True` the declared in-body
        payloads must ALSO all be bf16 and pair 1:1 with the compiled
        census — otherwise the halved `collective_bytes_per_sp` the
        budget pins would be priced off a payload the program never
        declared.
        """
        surf = self.spec.bf16_surface
        if surf is None:
            return []
        name = self.spec.name
        allowed = frozenset(surf.allowed_kinds)
        out: List[str] = []
        compute = 0
        # Collectives are detected through the payload scanner, NOT the
        # per-line bf16 scan: a region-form all_reduce's op line does
        # not carry its payload type (it sits on the region-closing
        # line), so a line scan would see only the scalar region add.
        for p in self.declared_collective_payloads:
            if p.result_dtype == "bf16" and not surf.collectives:
                out.append(
                    f"{name}: bf16 collective payload without a "
                    f"declared bf16_collectives surface — line {p.line}")
        for op in hlo.bf16_stablehlo_ops(self.stablehlo):
            if op.kind in hlo.COLLECTIVE_KINDS:
                continue  # payload-checked above
            if op.kind == "add" and op.result_scalar and surf.collectives:
                continue  # a declared collective's reduction region
            if op.kind in _BF16_ACCUM_KINDS:
                if op.kind == "dot_general":
                    if op.result_dtype == "bf16":
                        out.append(
                            f"{name}: dot_general ACCUMULATES in bf16 "
                            f"(preferred_element_type dropped?) — line "
                            f"{op.line}: {op.text[:120]}")
                    else:
                        compute += 1
                    continue
                out.append(
                    f"{name}: bf16 accumulation ({op.kind}) — the "
                    f"surface contract is bf16 storage with f32 "
                    f"accumulation — line {op.line}: {op.text[:120]}")
                continue
            if op.kind == "convert":
                # Only FLOAT-family crossings are leaks (f64/f16 would
                # smuggle a different precision family in); an integer
                # operand cast to bf16 (the 2-D tile masks) is exact.
                bad = [d for d in op.dtypes
                       if d not in ("bf16", "f32")
                       and (d.startswith("f") or d.startswith("c"))]
                if bad:
                    out.append(
                        f"{name}: convert crosses bf16<->{bad[0]} "
                        f"(family leak; only bf16<->f32 is on the "
                        f"surface) — line {op.line}: {op.text[:120]}")
                continue
            if op.kind not in allowed:
                out.append(
                    f"{name}: bf16 tensor in op kind {op.kind!r} "
                    f"outside the declared surface — line {op.line}: "
                    f"{op.text[:120]}")
                continue
            if op.kind == "multiply" and op.result_dtype == "bf16":
                compute += 1
        if compute < surf.min_compute_ops:
            out.append(
                f"{name}: declared bf16 surface carries only {compute} "
                f"bf16 compute op(s) (< {surf.min_compute_ops}) — the "
                "operands were silently upcast and the program runs "
                "f32 math under a bf16 flag")
        if surf.collectives:
            body = self.pcg_body_collectives()
            if body and self._declared_payload_bytes(body) is None:
                out.append(
                    f"{name}: compiled PCG-body collectives could not "
                    "be paired with declared StableHLO payloads — the "
                    "byte axis cannot certify the bf16 wire payload")
            # Every in-body declared payload must be bf16.  SCOPE NOTE:
            # this assumes the edge-local preconditioner families
            # (JACOBI/NEUMANN/SCHUR_DIAG — the current bf16 canonical
            # programs), where every in-body collective belongs to the
            # compressed S·p matvec.  A future bf16 canonical program
            # with a TWO_LEVEL/MULTILEVEL precond would carry
            # legitimate FULL-precision coarse-correction psums in the
            # body (the documented contract — solver/pcg.py): scope
            # this check to the matvec's census before declaring such
            # a spec, or it will flag the f32 coarse payloads.
            declared = [op for op in self.declared_collective_payloads
                        if op.while_depth >= 2]
            for op in declared:
                if op.result_dtype != "bf16":
                    out.append(
                        f"{name}: in-body collective declares a "
                        f"{op.result_dtype} payload under a "
                        f"bf16-collectives surface (compression "
                        f"dropped) — line {op.line}")
        return out

    def donation_violations(self) -> List[str]:
        got = hlo.aliased_parameters(self.compiled_text)
        want = frozenset(self.spec.donate_leaves)
        out: List[str] = []
        missing = sorted(want - got)
        if missing:
            out.append(
                f"{self.spec.name}: declared donation of parameter(s) "
                f"{missing} did not materialise as input-output aliasing "
                "in the compiled executable (buffer savings silently "
                "lost; did an output stop aliasing its input?)")
        unexpected = sorted(got - want)
        if unexpected:
            out.append(
                f"{self.spec.name}: parameter(s) {unexpected} alias "
                "outputs without a declared donation (audit expectation "
                "out of date — update ProgramSpec.donate_leaves)")
        return out

    # ---- pass 4: budget metrics --------------------------------------
    def metrics(self) -> Dict[str, float]:
        other = [op for op in self.collectives if op.kind != "all_reduce"]
        out = {
            "flops": float(self.flops),
            "bytes_accessed": float(self.bytes_accessed),
            "peak_temp_bytes": float(self.peak_temp_bytes),
            "argument_bytes": float(self.argument_bytes),
            "output_bytes": float(self.output_bytes),
        }
        # A backend without cost/memory analysis yields -1 sentinels:
        # OMIT those rather than letting "-1" flow into the budget gate
        # as a measurement (budget.compare reports a gated metric that
        # went missing, so the gate degrades loudly, not silently).
        out = {k: v for k, v in out.items() if v >= 0.0}
        out["all_reduce_count"] = float(
            sum(1 for op in self.collectives if op.kind == "all_reduce"))
        out["other_collective_count"] = float(len(other))
        # Bytes-moved-per-iteration axis (ROADMAP item 3): ring-model
        # bytes each device moves per CG step (the PCG body executes
        # once per iteration), from per-op operand bytes x replica-group
        # shape.  Exact-match gated (budget.TOLERANCES) so an overlap /
        # subgroup win is PINNED, not anecdotal — and a fatter
        # collective sneaking into the body fails audit --check.
        out["collective_bytes_per_sp"] = self.pcg_body_collective_bytes()
        # Declared analytical axes (analysis/edge_budget.py): priced
        # from the spec, not measured from the backend, so they are
        # present — and exact-gated — even when cost_analysis is not.
        if self.spec.sp_budget is not None:
            for k, v in self.spec.sp_budget:
                out[k] = float(v)
        return out

    def violations(self) -> List[str]:
        return (self.transfer_violations() + self.collective_violations()
                + self.dtype_violations() + self.bf16_surface_violations()
                + self.donation_violations())

    def summary(self) -> Dict[str, object]:
        """JSON-able per-program audit summary (the audit CLI's
        `--summary` lines)."""
        pcg = self.pcg_body_collectives()
        return {
            "program": self.spec.name,
            "metrics": self.metrics(),
            "pcg_body_all_reduces": sum(
                1 for op in pcg if op.kind == "all_reduce"),
            "pcg_body_census": self.pcg_body_kind_census(),
            # Opaque-code census: every custom_call target in the
            # StableHLO, counted.  A Pallas kernel lowers to one
            # (tpu_custom_call) on TPU; the canonical fused-OFF
            # programs must stay kernel-free here (dark-launch pin,
            # tests/test_fused.py).
            "custom_calls": hlo.custom_call_census(self.stablehlo_ops),
            "collectives": [
                {"kind": op.kind, "elems": op.result_elems,
                 "dtype": op.result_dtype, "scope": op.op_name,
                 "group_size": op.group_size(self.spec.world),
                 "bytes_moved": hlo.collective_bytes_moved(
                     op, self.spec.world)}
                for op in self.collectives
            ],
            "violations": self.violations(),
        }


# --------------------------------------------------------------------------
# Canonical programs.  Sizes are deliberately tiny (lowering cost, not
# solve cost, dominates) but non-degenerate: enough edges to pad to one
# EDGE_QUANTUM per shard, both loops live, every psum site reachable.
# --------------------------------------------------------------------------

def _ba_problem():
    from megba_tpu.io.synthetic import make_synthetic_bal

    return make_synthetic_bal(
        num_cameras=4, num_points=24, obs_per_point=3, seed=0,
        param_noise=4e-2, pixel_noise=0.3, dtype=np.float32)


def _ba_option():
    from megba_tpu.common import AlgoOption, ProblemOption, SolverOption

    return ProblemOption(
        dtype=np.float32,
        algo_option=AlgoOption(max_iter=3),
        solver_option=SolverOption(max_iter=8, tol=1e-8))


def _ba_ml_problem():
    # The multilevel canonical program needs a camera graph big enough
    # to plan >= 2 coarse levels (the 4-camera problem aggregates to 2
    # clusters, under the hierarchy's own coarsest floor) — a small
    # RING-locality scene, the structure the operator targets.
    from megba_tpu.io.synthetic import make_synthetic_bal

    return make_synthetic_bal(
        num_cameras=12, num_points=60, obs_per_point=3, seed=0,
        param_noise=4e-2, pixel_noise=0.3, dtype=np.float32,
        locality="ring")


def _lower_ba(world: int, use_tiled: bool, forcing: bool = False,
              guarded: bool = False, twolevel: bool = False,
              multilevel: bool = False, mesh2d: bool = False,
              bf16: bool = False):
    import dataclasses as _dc

    from megba_tpu.common import JacobianMode, RobustOption, SolverOption
    from megba_tpu.ops.residuals import make_residual_jacobian_fn
    from megba_tpu.solve import flat_solve

    s = _ba_ml_problem() if multilevel else _ba_problem()
    option = _ba_option()
    if world > 1:
        option = _dc.replace(option, world_size=world)
    if mesh2d:
        # 2-D mesh canonical program: world 4 factored 2x2 — the
        # subgroup-collective matvec pipeline on the SAME tiny problem
        # as the 1-D programs, so the bytes-moved axis is comparable
        # operand-for-operand.
        option = _dc.replace(option, solver_option=_dc.replace(
            option.solver_option, mesh_2d=True, cam_blocks=2))
    if forcing:
        # Inexact-LM canonical program: adaptive Eisenstat-Walker
        # forcing (eta_k a traced while-carry scalar) + warm starts.
        option = _dc.replace(option, solver_option=SolverOption(
            max_iter=8, tol=1e-1, forcing=True, warm_start=True))
    if guarded:
        # Fault-containment canonical program: LM rollback/recovery +
        # PCG breakdown restarts armed (robustness layer).
        option = _dc.replace(option, robust_option=RobustOption(guards=True))
    if twolevel:
        # Two-level preconditioner canonical program: the camera-graph
        # coarse space rides as a DeviceClusterPlan operand (flat_solve
        # plans + caches it) and the cycle runs inside the fused PCG
        # body (solver/precond.py).
        from megba_tpu.common import PrecondKind

        option = _dc.replace(option, solver_option=_dc.replace(
            option.solver_option, precond=PrecondKind.TWO_LEVEL))
    if multilevel:
        # Recursive-hierarchy canonical program: the
        # DeviceMultiLevelPlan operand carries the level-1 cluster plan
        # + coarse assignment chain; every per-level Galerkin build
        # (edge-scale level 1, dense above) lives OUTSIDE pcg_core.
        from megba_tpu.common import PrecondKind

        option = _dc.replace(option, solver_option=_dc.replace(
            option.solver_option, precond=PrecondKind.MULTILEVEL,
            coarsen_factor=2.0, max_levels=3))
    if bf16:
        # bf16 MXU pipeline canonical programs: storage + collective
        # gates BOTH on — the full rung, so the allowed-surface pass
        # and the halved bytes axis are pinned together.
        option = _dc.replace(option, solver_option=_dc.replace(
            option.solver_option, bf16=True, bf16_collectives=True))
    f = make_residual_jacobian_fn(mode=JacobianMode.AUTODIFF)
    return flat_solve(f, s.cameras0, s.points0, s.obs, s.cam_idx, s.pt_idx,
                      option, use_tiled=use_tiled, lower_only=True)


def _lower_batched(lanes: int):
    """The serving layer's batched mega-solve (vmapped LM, lane axis 4).

    Lowered through the compile pool's own AOT entry point
    (serving/compile_pool.lower_bucket) — the same builder, operand
    layout and donation flags every fleet dispatch uses — at the shape
    class the canonical tiny BA problem buckets to under the default
    ladder."""
    from megba_tpu.common import JacobianMode
    from megba_tpu.ops.residuals import make_residual_jacobian_fn
    from megba_tpu.serving.compile_pool import lower_bucket
    from megba_tpu.serving.shape_class import BucketLadder, classify

    s = _ba_problem()
    option = _ba_option()
    shape = classify(s.cameras0.shape[0], s.points0.shape[0],
                     s.obs.shape[0], option.dtype, BucketLadder())
    engine = make_residual_jacobian_fn(mode=JacobianMode.AUTODIFF)
    return lower_bucket(engine, option, shape, lanes,
                        cd=s.cameras0.shape[1], pd=s.points0.shape[1],
                        od=s.obs.shape[1])


def _lower_pgo(world: int):
    from megba_tpu.common import AlgoOption, ProblemOption, SolverOption
    from megba_tpu.models.pgo import make_synthetic_pose_graph, solve_pgo

    g = make_synthetic_pose_graph(num_poses=16, loop_closures=4, seed=1)
    option = ProblemOption(
        dtype=np.float64, world_size=world,
        algo_option=AlgoOption(max_iter=3),
        solver_option=SolverOption(max_iter=8, tol=1e-10))
    return solve_pgo(g.poses0, g.edge_i, g.edge_j, g.meas, option,
                     lower_only=True)


def _lower_factor(factor: str, dtype=np.float32):
    """Canonical program of one registered Schur factor family, lowered
    through flat_solve's registry dispatch (the production seam every
    factor solve rides — engine resolution included)."""
    import dataclasses as _dc

    from megba_tpu.solve import flat_solve

    if factor == "rig":
        from megba_tpu.factors.rig import make_synthetic_rig

        s = make_synthetic_rig(num_bodies=4, num_points=24, seed=0,
                               dtype=dtype)
    elif factor == "pinhole_radial":
        from megba_tpu.factors.radial import make_synthetic_radial

        s = make_synthetic_radial(num_cameras=4, num_points=24, seed=0,
                                  dtype=dtype)
    elif factor == "pose_prior":
        from megba_tpu.factors.priors import make_synthetic_priors

        s = make_synthetic_priors(num_poses=8, seed=0, dtype=dtype)
    else:
        raise ValueError(f"no canonical problem for factor {factor!r}")
    option = _dc.replace(_ba_option(), dtype=dtype)
    return flat_solve(None, s.cameras0, s.points0, s.obs, s.cam_idx,
                      s.pt_idx, option, use_tiled=False, factor=factor,
                      lower_only=True)


def _lower_sim3(world: int):
    from megba_tpu.common import AlgoOption, ProblemOption, SolverOption
    from megba_tpu.factors.sim3 import make_synthetic_sim3_graph
    from megba_tpu.models.pgo import solve_pgo

    g = make_synthetic_sim3_graph(num_poses=16, loop_closures=4, seed=1)
    option = ProblemOption(
        dtype=np.float64, world_size=world,
        algo_option=AlgoOption(max_iter=3),
        solver_option=SolverOption(max_iter=8, tol=1e-10))
    return solve_pgo(g.poses0, g.edge_i, g.edge_j, g.meas, option,
                     factor="sim3_between", lower_only=True)


# Donated leaves of the sharded programs: flat_solve donates (cameras,
# points), solve_pgo its poses; the audit expects what production does.
_SHARDED_DONATION = (0, 1)
_PGO_SHARDED_DONATION = (0,)


# --------------------------------------------------------------------------
# Declared per-S·p budgets (edge_budget.py pricing over the SAME host
# planning the lowering runs — the lru caches make the later build a
# plan-cache hit, so the audit never plans twice).  Everything is
# derived live: if the quantum, a tile plan, or the bucket ladder
# changes, the priced number moves WITH the program and the committed
# ANALYSIS_BUDGET.json entry fails exact-match, naming the drift.
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sp_budget_ba(world: int, use_tiled: bool, mesh2d: bool = False,
                  bf16: bool = False, multilevel: bool = False,
                  lanes: int = 1,
                  factor: Optional[str] = None,
                  ) -> Tuple[Tuple[str, float], ...]:
    from megba_tpu.analysis import edge_budget
    from megba_tpu.core.fm import EDGE_QUANTUM

    fam = "f32"
    rd = 2  # BAL / rig / radial pinhole residual rows
    if factor == "rig":
        from megba_tpu.factors.rig import make_synthetic_rig

        s = make_synthetic_rig(num_bodies=4, num_points=24, seed=0,
                               dtype=np.float32)
    elif factor == "pinhole_radial":
        from megba_tpu.factors.radial import make_synthetic_radial

        s = make_synthetic_radial(num_cameras=4, num_points=24, seed=0,
                                  dtype=np.float32)
    elif factor == "pose_prior":
        from megba_tpu.factors.priors import make_synthetic_priors

        s = make_synthetic_priors(num_poses=8, seed=0, dtype=np.float64)
        fam, rd = "f64", 6
    else:
        s = _ba_ml_problem() if multilevel else _ba_problem()
    nc, cd = s.cameras0.shape
    npts, pd = s.points0.shape
    ne = s.obs.shape[0]
    if lanes > 1:
        # The batched program solves at its BUCKET shape (the compile
        # pool's ladder), not the raw problem shape.
        from megba_tpu.serving.shape_class import BucketLadder, classify

        shape = classify(nc, npts, ne, np.float32, BucketLadder())
        nc, npts, ne = shape.n_cam, shape.n_pt, shape.n_edge
    if mesh2d:
        from megba_tpu.ops.segtiles import cached_camera_tile_plan
        from megba_tpu.parallel.mesh import factor_mesh_2d

        n_shards, n_blocks = factor_mesh_2d(world, 2)
        (tplan, _), _ = cached_camera_tile_plan(
            s.cam_idx, s.pt_idx, nc, npts, n_shards, n_blocks)
        slots = tplan.perm.shape[0] // world  # one (shard, block) cell
    elif use_tiled:
        from megba_tpu.ops.segtiles import cached_dual_plans

        (plan_c, _), _ = cached_dual_plans(s.cam_idx, s.pt_idx, nc, npts)
        slots = plan_c.n_slots
    else:
        q = world * EDGE_QUANTUM
        slots = (-(-ne // q) * q) // world
    b = edge_budget.schur_sp_budget(
        nc, cd, npts, pd, rd, slots,
        operand="bf16" if bf16 else fam, param=fam, acc=fam, lanes=lanes)
    return tuple(sorted(b.items()))


@functools.lru_cache(maxsize=None)
def _sp_budget_pgo(world: int,
                   pose_dim: int = 6) -> Tuple[Tuple[str, float], ...]:
    # The canonical pose graphs: 16 poses, 15 odometry + 4 loop edges,
    # padded to a multiple of world (models/pgo.py pads by world, not
    # by EDGE_QUANTUM); residual rows = pose_dim for both SE(3) and
    # Sim(3).
    ne = 19
    slots = (ne + (-ne) % world) // world
    from megba_tpu.analysis import edge_budget

    b = edge_budget.pgo_sp_budget(16, pose_dim, pose_dim, slots)
    return tuple(sorted(b.items()))


def program_specs() -> Dict[str, ProgramSpec]:
    """name -> spec for every canonical audited program."""
    return {
        "ba_single_f32": ProgramSpec(
            name="ba_single_f32", float_family="f32", world=1, pcg_psums=0,
            donate_leaves=(0, 1),
            sp_budget=_sp_budget_ba(world=1, use_tiled=False),
            build=lambda: _lower_ba(world=1, use_tiled=False)),
        "ba_tiled_f32": ProgramSpec(
            name="ba_tiled_f32", float_family="f32", world=1, pcg_psums=0,
            donate_leaves=(0, 1),
            sp_budget=_sp_budget_ba(world=1, use_tiled=True),
            build=lambda: _lower_ba(world=1, use_tiled=True)),
        "ba_sharded_w2_f32": ProgramSpec(
            name="ba_sharded_w2_f32", float_family="f32", world=2,
            # Schur S·p = Hpp p - Hpl Hll^-1 Hlp p: one psum in hlp, one
            # in hpl — exactly two reductions per CG step (solver/pcg.py).
            pcg_psums=2,
            donate_leaves=_SHARDED_DONATION,
            sp_budget=_sp_budget_ba(world=2, use_tiled=False),
            build=lambda: _lower_ba(world=2, use_tiled=False)),
        "ba_forcing_w2_f32": ProgramSpec(
            name="ba_forcing_w2_f32", float_family="f32", world=2,
            # Inexact LM (forcing + warm_start): the adaptive tolerance
            # is a traced carry scalar and the warm-start r0 = b - S x0
            # / recurrence-priming S·u0 products live OUTSIDE the PCG
            # while body, so the per-CG-step census is UNCHANGED —
            # exactly two all-reduces.  Adaptive forcing adding a
            # collective or a host transfer is precisely the regression
            # this spec pins against.
            pcg_psums=2,
            donate_leaves=_SHARDED_DONATION,
            sp_budget=_sp_budget_ba(world=2, use_tiled=False),
            build=lambda: _lower_ba(world=2, use_tiled=False,
                                    forcing=True)),
        "ba_guarded_w2_f32": ProgramSpec(
            name="ba_guarded_w2_f32", float_family="f32", world=2,
            # RobustOption guards: detection reads only the already-
            # psum-reduced scalars (NaN propagates through the existing
            # reductions) and the PCG restart reuses the body's single
            # matvec slot, so the guarded while body carries EXACTLY the
            # same two all-reduces as the unguarded Schur solve — a
            # guard that added a sync or a host transfer is precisely
            # the regression this spec pins against.
            pcg_psums=2,
            donate_leaves=_SHARDED_DONATION,
            sp_budget=_sp_budget_ba(world=2, use_tiled=False),
            build=lambda: _lower_ba(world=2, use_tiled=False,
                                    guarded=True)),
        "ba_twolevel_w2_f32": ProgramSpec(
            name="ba_twolevel_w2_f32", float_family="f32", world=2,
            # Two-level Schur preconditioner: the coarse-space build
            # psums V and G ONCE per PCG solve (outside the while
            # body), and the per-apply cycle is replicated dense work
            # on materialised G/A_c — so the while-BODY census stays
            # exactly two all-reduces per S·p, identical to plain
            # block-Jacobi.  A coarse correction that added an in-body
            # collective (e.g. a naive matrix-free R S Rᵀ apply) is
            # precisely the regression this spec pins against.
            pcg_psums=2,
            donate_leaves=_SHARDED_DONATION,
            sp_budget=_sp_budget_ba(world=2, use_tiled=False),
            build=lambda: _lower_ba(world=2, use_tiled=False,
                                    twolevel=True)),
        "ba_multilevel_w2_f32": ProgramSpec(
            name="ba_multilevel_w2_f32", float_family="f32", world=2,
            # Recursive multilevel Schur preconditioner (3-level
            # hierarchy on a ring-locality scene): the level-1 coarse
            # build psums V and G once per PCG solve and every DEEPER
            # level is a replicated dense Galerkin contraction with
            # ZERO collectives of its own — so the while-BODY census
            # stays exactly two all-reduces per S·p, identical to
            # block-Jacobi and the two-level cycle.  A hierarchy level
            # that added an in-body collective (or a per-level build
            # that slid inside pcg_core) is precisely the regression
            # this spec pins against.
            pcg_psums=2,
            donate_leaves=_SHARDED_DONATION,
            sp_budget=_sp_budget_ba(world=2, use_tiled=False,
                                     multilevel=True),
            build=lambda: _lower_ba(world=2, use_tiled=False,
                                    multilevel=True)),
        "ba_2d_w4_f32": ProgramSpec(
            name="ba_2d_w4_f32", float_family="f32", world=4,
            # 2-D (2 edge shards x 2 camera blocks) mesh: the matvec's
            # two WORLD all-reduces become subgroup stages — one
            # psum_scatter over the camera subgroup + one edge-subgroup
            # psum on the point side, C-1 double-buffered
            # collective_permutes rotating the point shard, and one
            # edge-subgroup psum + camera-subgroup all_gather on the
            # camera side.  Exactly 2 all-reduces remain in the body
            # (both EDGE-subgroup), every body collective is pinned
            # subgroup-scoped (group size 2 < world 4), and the
            # bytes-moved axis must come in strictly below the 1-D
            # all-reduce scaling law (tests/test_program_audit.py
            # asserts the comparison against ba_sharded_w2_f32's law).
            pcg_psums=2,
            donate_leaves=_SHARDED_DONATION,
            allowed_kinds=("all_reduce", "reduce_scatter", "all_gather",
                           "collective_permute"),
            pcg_body_census=(("all_reduce", 2), ("reduce_scatter", 1),
                             ("all_gather", 1), ("collective_permute", 1)),
            pcg_subgroup_only=True,
            sp_budget=_sp_budget_ba(world=4, use_tiled=False,
                                     mesh2d=True),
            build=lambda: _lower_ba(world=4, use_tiled=False,
                                    mesh2d=True)),
        "ba_bf16_w2_f32": ProgramSpec(
            name="ba_bf16_w2_f32", float_family="f32", world=2,
            # The bf16 MXU pipeline on the 1-D mesh: per-edge products
            # on bf16 operands with f32 accumulation, bf16 M⁻¹ apply,
            # and bf16 in-body collective payloads.  The body census
            # stays exactly two all-reduces per S·p (the textbook-
            # recurrence body has the same matvec-only collective
            # site); the allowed-surface pass pins bf16 to the
            # declared op kinds with f32 accumulation, and the budget
            # entry pins `collective_bytes_per_sp` at exactly HALF
            # ba_sharded_w2_f32's (tests/test_program_audit.py asserts
            # the ratio) — priced at the DECLARED payload (see
            # bf16_surface field note).
            pcg_psums=2,
            donate_leaves=_SHARDED_DONATION,
            bf16_surface=Bf16Surface(collectives=True),
            sp_budget=_sp_budget_ba(world=2, use_tiled=False,
                                     bf16=True),
            build=lambda: _lower_ba(world=2, use_tiled=False,
                                    bf16=True)),
        "ba_bf16_2d_w4_f32": ProgramSpec(
            name="ba_bf16_2d_w4_f32", float_family="f32", world=4,
            # The bf16 pipeline composed with PR 14's 2-D mesh: the
            # same subgroup-scoped five-collective census as
            # ba_2d_w4_f32, every payload bf16 on the wire — the
            # budget entry pins the bytes axis at exactly half the f32
            # 2-D program's.  This is the pod-scale configuration the
            # rung exists for: subgroup scoping divides the payload by
            # the mesh factor, bf16 halves what remains.
            pcg_psums=2,
            donate_leaves=_SHARDED_DONATION,
            allowed_kinds=("all_reduce", "reduce_scatter", "all_gather",
                           "collective_permute"),
            pcg_body_census=(("all_reduce", 2), ("reduce_scatter", 1),
                             ("all_gather", 1), ("collective_permute", 1)),
            pcg_subgroup_only=True,
            bf16_surface=Bf16Surface(collectives=True),
            sp_budget=_sp_budget_ba(world=4, use_tiled=False,
                                     mesh2d=True, bf16=True),
            build=lambda: _lower_ba(world=4, use_tiled=False,
                                    mesh2d=True, bf16=True)),
        "ba_batched_b4_f32": ProgramSpec(
            name="ba_batched_b4_f32", float_family="f32", world=1,
            # The batched program is a vmap over a LANE axis on one
            # device: per-lane convergence masking is pure selects, so
            # a collective (or a host transfer) appearing here means
            # the serving layer broke the fleet contract.
            pcg_psums=0,
            # The batcher donates the stacked parameter lanes
            # (compile_pool._build_batched_solve donate_argnums=(0, 1)).
            donate_leaves=(0, 1),
            sp_budget=_sp_budget_ba(world=1, use_tiled=False, lanes=4),
            build=lambda: _lower_batched(lanes=4)),
        # ---- factor-registry canonical programs ----------------------
        # One per new family (ISSUE 13): each is lowered through the
        # registry seam itself (flat_solve(factor=...) / solve_pgo
        # (factor=...)), so the audited program IS what a registered-
        # factor solve dispatches — a registry refactor that changed
        # the lowering, leaked a dtype through a new residual, or added
        # a collective fails this gate, exactly like the BAL/PGO
        # originals.
        "ba_rig_single_f32": ProgramSpec(
            name="ba_rig_single_f32", float_family="f32", world=1,
            # Single device: the rig's shared-body-block Schur solve
            # must carry zero collectives like every single-device
            # program.
            pcg_psums=0,
            donate_leaves=(0, 1),
            sp_budget=_sp_budget_ba(world=1, use_tiled=False,
                                     factor="rig"),
            build=lambda: _lower_factor("rig")),
        "ba_radial_single_f32": ProgramSpec(
            name="ba_radial_single_f32", float_family="f32", world=1,
            pcg_psums=0,
            donate_leaves=(0, 1),
            sp_budget=_sp_budget_ba(world=1, use_tiled=False,
                                     factor="pinhole_radial"),
            build=lambda: _lower_factor("pinhole_radial")),
        "prior_single_f64": ProgramSpec(
            name="prior_single_f64", float_family="f64", world=1,
            # The unary-prior family runs f64 (its GPS/marginalization
            # use cases are precision-sensitive), exercising the
            # inverse dtype census on a registry factor: an f32 leak in
            # the prior residual's rotation chain fails here.
            pcg_psums=0,
            donate_leaves=(0, 1),
            sp_budget=_sp_budget_ba(world=1, use_tiled=False,
                                     factor="pose_prior"),
            build=lambda: _lower_factor("pose_prior", np.float64)),
        "pgo_sim3_single_f64": ProgramSpec(
            name="pgo_sim3_single_f64", float_family="f64", world=1,
            # The sim(3) family rides the genericized PGO driver; its
            # 7-dof blocks must lower collective-free on one device
            # exactly like the SE(3) program.
            pcg_psums=0,
            donate_leaves=(0,),
            sp_budget=_sp_budget_pgo(world=1, pose_dim=7),
            build=lambda: _lower_sim3(world=1)),
        "pgo_single_f64": ProgramSpec(
            name="pgo_single_f64", float_family="f64", world=1, pcg_psums=0,
            donate_leaves=(0,),
            sp_budget=_sp_budget_pgo(world=1),
            build=lambda: _lower_pgo(world=1)),
        "pgo_sharded_w2_f64": ProgramSpec(
            name="pgo_sharded_w2_f64", float_family="f64", world=2,
            # PGO's matrix-free H·x has a single segment-reduce psum
            # (models/pgo.py matvec) — one reduction per CG step.
            pcg_psums=1,
            donate_leaves=_PGO_SHARDED_DONATION,
            sp_budget=_sp_budget_pgo(world=2),
            build=lambda: _lower_pgo(world=2)),
    }


def audit_program(spec: ProgramSpec,
                  lowered: Optional[object] = None) -> ProgramAudit:
    """Lower (unless given), compile, and census one canonical program."""
    lowered = spec.build() if lowered is None else lowered
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jaxlib: one dict per device
        ca = ca[0] if ca else {}
    try:
        mem = compiled.memory_analysis()
    except Exception:  # backend without memory analysis
        mem = None
    return ProgramAudit(
        spec=spec,
        stablehlo=lowered.as_text(),
        compiled_text=compiled.as_text(),
        source_hlo=lowered.as_text(dialect="hlo", debug_info=True),
        flops=float(ca.get("flops", -1.0)),
        bytes_accessed=float(ca.get("bytes accessed", -1.0)),
        peak_temp_bytes=float(
            getattr(mem, "temp_size_in_bytes", -1) if mem else -1),
        argument_bytes=float(
            getattr(mem, "argument_size_in_bytes", -1) if mem else -1),
        output_bytes=float(
            getattr(mem, "output_size_in_bytes", -1) if mem else -1),
    )


def audit_all(names: Optional[List[str]] = None) -> Dict[str, ProgramAudit]:
    specs = program_specs()
    if names:
        unknown = sorted(set(names) - set(specs))
        if unknown:
            raise ValueError(
                f"unknown program(s) {unknown}; known: {sorted(specs)}")
        specs = {n: specs[n] for n in names}
    return {name: audit_program(spec) for name, spec in specs.items()}
