"""Layer: coordinate descent. Device self time of the traced whole fits
under the scope ``game_re.variance`` — the buckets' per-entity FULL
variances: the weighted Gram, its Cholesky factor, the diagonal of the
inverse — per random-effect coordinate update. None for a program that
computes no variances in the one-dispatch update (no such scope)."""
from benchmark.lib.game_scopes import phase_ms_per_re_update


def read(ctx):
    return phase_ms_per_re_update(ctx, ("game_re.variance",))
