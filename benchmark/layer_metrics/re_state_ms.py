"""Layer: coordinate descent. Device self time of the traced whole fits in
the L-BFGS history inside the per-entity solves — scopes ``lbfgs.two_loop``
and ``lbfgs.push`` under ``game_re.solve`` — per random-effect coordinate
update: the part of ``re_solve_ms`` that reads no block."""
from benchmark.lib.game_scopes import phase_ms_per_re_update


def read(ctx):
    return phase_ms_per_re_update(ctx, ("lbfgs.two_loop", "lbfgs.push"),
                                  inside="game_re.solve")
