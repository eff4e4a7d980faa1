"""Layer: solver. Device self time inside the traced whole solves under the
scope ``lbfgs.linesearch`` — the Wolfe search's own state machine plus the
``objective.loss`` evaluations nested under it (elementwise on cached
margins: no X pass) — per lock-step solver iteration."""
from benchmark.lib.scope_reduce import scope_ms_per_iteration


def read(ctx):
    return scope_ms_per_iteration(
        ctx, lambda chain: "lbfgs.linesearch" in chain
        and chain[-1] in ("lbfgs.linesearch", "objective.loss"))
