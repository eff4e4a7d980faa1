"""Device time of the coordinate-descent scopes (`game_re.*`, `game_fixed.*`,
`game.objective`) of the traced whole fits, per random-effect update.

A scope CONTAINS what nests under it: the per-entity solves run the L-BFGS
and X-pass scopes inside ``game_re.solve``, and `scope_reduce` puts an
event's self time to its innermost scope, so a phase here is every chain
that passes through the phase's scope.
"""
from __future__ import annotations

from benchmark.lib.scope_reduce import unit_scopes


def phase_ms_per_re_update(ctx: dict, phases: tuple, inside: str = None):
    """Milliseconds per random-effect coordinate update of the traced units
    spent in chains through any scope of ``phases`` (a name ending in "."
    stands for every scope it starts) and, where given, also through the
    scope ``inside``; ``None`` where there is no scope table, no such chain
    in it (a program from before the scopes), or no update."""
    updates = sum(r.get("re_updates", 0)
                  for r in ctx["results"].get("unit", []))
    table = unit_scopes()
    if table is None or not updates:
        return None

    def through(part: str) -> bool:
        return any(part == p or (p.endswith(".") and part.startswith(p))
                   for p in phases)

    hits = [v for key, v in table["chains"].items()
            if any(through(part) for part in key.split(">"))
            and (inside is None or inside in key.split(">"))]
    if not hits:
        return None
    return sum(hits) / updates * 1e3
