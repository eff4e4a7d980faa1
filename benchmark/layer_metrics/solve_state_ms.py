"""Layer: solver. Device self time inside the traced whole solves under the
scopes ``lbfgs.two_loop`` and ``lbfgs.push`` (the passes over the (m, d[, G])
history), per lock-step solver iteration."""
from benchmark.lib.scope_reduce import scope_ms_per_iteration

STATE = ("lbfgs.two_loop", "lbfgs.push")


def read(ctx):
    return scope_ms_per_iteration(ctx, lambda chain: chain[-1] in STATE)
