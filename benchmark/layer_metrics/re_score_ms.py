"""Layer: coordinate descent. Device self time of the traced whole fits
under the scope ``game_re.score`` — per-row margins read from the (E, d)
coefficient table, over the training rows after every update and over the
validation rows once a fit — per random-effect coordinate update."""
from benchmark.lib.game_scopes import phase_ms_per_re_update


def read(ctx):
    return phase_ms_per_re_update(ctx, ("game_re.score",))
