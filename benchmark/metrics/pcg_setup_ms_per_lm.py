"""Device ms per LM iteration in the PCG solve outside its CG loop:
megba.pcg_setup (damping, Hll^-1, the preconditioner build, the reduced
right-hand side), megba.pcg_backsub (the point back-substitution) and
whatever else lies under megba.pcg but not megba.pcg_core, traced
solve.  A trace reduction that does not list the two inner scopes
counts their ops under megba.pcg, so the sum reads the same either way."""

PARTS = ("megba.pcg", "megba.pcg_setup", "megba.pcg_backsub")


def read(ctx):
    trace, units = ctx.get("trace"), ctx.get("traced_lm_iters")
    if trace is None or not units or "megba.pcg_core" not in trace.scope_s:
        return None
    return sum(trace.scope_s.get(k, 0.0) for k in PARTS) / units * 1e3
