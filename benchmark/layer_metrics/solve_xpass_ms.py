"""Layer: X pass. Device self time INSIDE the traced whole solves under the
program's ``xpass.*`` scopes (forward and transposed passes: hot-block
matmuls, blocked-ELL gathers, row reassembly), per lock-step solver
iteration. The once-per-solve prologue's passes are in it, 1 in 41."""
from benchmark.lib.scope_reduce import scope_ms_per_iteration


def read(ctx):
    return scope_ms_per_iteration(
        ctx, lambda chain: chain[-1].startswith("xpass."))
