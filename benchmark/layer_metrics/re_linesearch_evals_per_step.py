"""Layer: coordinate descent. Line-search evaluations per per-entity solver
iteration over the traced whole fits: the program's counters
``game_re.linesearch_trials`` ÷ ``game_re.iterations`` (Σ over entities,
device values until the report is asked for). A lane past its stall loses
every search it starts, so this rises with the share of repeated points."""


def read(ctx):
    counters = ctx["telemetry"]["counters"]
    iterations = counters.get("game_re.iterations")
    if not iterations or "game_re.linesearch_trials" not in counters:
        return None
    return counters["game_re.linesearch_trials"] / iterations
