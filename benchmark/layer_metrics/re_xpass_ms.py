"""Layer: coordinate descent. Device self time of the traced whole fits in
the passes over the blocks inside the per-entity solves — the ``xpass.*``
scopes under ``game_re.solve`` — per random-effect coordinate update: the
part of ``re_solve_ms`` that `re_solve_hbm_share`'s bytes are moved in."""
from benchmark.lib.game_scopes import phase_ms_per_re_update


def read(ctx):
    return phase_ms_per_re_update(ctx, ("xpass.fwd", "xpass.t", "xpass."),
                                  inside="game_re.solve")
