"""Layer: coordinate descent. Device self time of the traced whole fits
under the scopes ``game_re.gather`` (offsets laid into the buckets' rows,
warm starts read from the (E, d) table through each bucket's index map) and
``game_re.scatter`` (results written back to the table), per random-effect
coordinate update."""
from benchmark.lib.game_scopes import phase_ms_per_re_update


def read(ctx):
    return phase_ms_per_re_update(ctx, ("game_re.gather", "game_re.scatter"))
