"""Layer: coordinate descent. Device self time of the traced whole fits
under the scope ``game_re.solve`` — the buckets' vmapped per-entity solves,
with the L-BFGS and X-pass scopes that nest under it — per random-effect
coordinate update."""
from benchmark.lib.game_scopes import phase_ms_per_re_update


def read(ctx):
    return phase_ms_per_re_update(ctx, ("game_re.solve",))
