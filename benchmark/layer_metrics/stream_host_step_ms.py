"""Layer: stream. Milliseconds per solver iteration the streamed L-BFGS
host loop spends BETWEEN passes in the traced whole solves, link and
device both waiting on it: the program's spans ``solve.host_step``
(direction and ray coefficients; the Wolfe search over cached margins, the
step and the host margin chain ``z += a·dz``; history push, convergence,
bookkeeping) ÷ the units' iterations (``steps``, as
``solve_iter_device_ms`` divides). A program without the span reports
nothing."""
from benchmark.lib.host_spans import span_sum


def read(ctx):
    seconds, count = span_sum(ctx, "solve.host_step")
    iterations = sum(r.get("steps", 0)
                     for r in ctx["results"].get("unit", []))
    return seconds / iterations * 1e3 if count and iterations else None
