"""Layer: solver (X pass included). Device busy time of the traced whole
solves per lock-step solver iteration (the largest `OptResult.iterations`
over the lanes of each solve). The result carries no evaluation count, so
line-search evaluations are inside this number."""


def read(ctx):
    units = ctx["results"].get("unit", [])
    iterations = sum(r.get("steps", 0) for r in units)
    if not iterations:
        return None
    return ctx["trace"]["sections"]["unit"]["busy_s"] / iterations * 1e3
