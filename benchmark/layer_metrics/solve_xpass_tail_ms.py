"""Layer: X pass. The part of ``solve_xpass_ms`` outside the hot block:
scopes ``xpass.fwd.tail`` and ``xpass.t.tail`` (blocked-ELL gathers and
their contractions) and ``xpass.fwd.reassemble`` (the `row_pos` gather),
per lock-step solver iteration."""
from benchmark.lib.scope_reduce import scope_ms_per_iteration

TAIL = ("xpass.fwd.tail", "xpass.t.tail", "xpass.fwd.reassemble")


def read(ctx):
    return scope_ms_per_iteration(ctx, lambda chain: chain[-1] in TAIL)
