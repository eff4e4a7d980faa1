"""Layer: coordinate descent. The share of the random-effect updates'
counted rows x iterations whose iteration did NOT lower its lane's loss:
1 - ``game_re.moved_row_iterations`` / ``game_re.row_iterations``. At
``tolerance`` 0 a solve runs its whole depth and a lane past its stall
repeats its last point; this is how much of `rows_iters_per_s`'s
random-effect work those repeats are."""


def read(ctx):
    counters = ctx["telemetry"]["counters"]
    total = counters.get("game_re.row_iterations")
    if not total or "game_re.moved_row_iterations" not in counters:
        return None
    return 100.0 * (1.0 - counters["game_re.moved_row_iterations"] / total)
