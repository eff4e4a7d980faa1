"""Layer: coordinate descent. Device self time of the traced whole grid
fits under the scope ``game_grid.select`` — each lane's validation metric
and the best lane, from the lanes' validation margins — per fit. None for
a program that selects on the host (no such scope)."""
from benchmark.lib.scope_reduce import unit_scopes


def read(ctx):
    n = len(ctx["results"].get("unit", []))
    table = unit_scopes()
    if table is None or not n:
        return None
    hits = [v for key, v in table["chains"].items()
            if "game_grid.select" in key.split(">")]
    if not hits:
        return None
    return sum(hits) / n * 1e3
