"""Layer: solver. Line-search evaluations per lock-step solver iteration
over the traced whole solves: the program's counters
``solver.linesearch_trials`` ÷ ``solver.iterations`` (both stay on the
device until the run's report is asked for). A program that does not count
them for a resident solve reports nothing."""


def read(ctx):
    counters = ctx["telemetry"]["counters"]
    iterations = counters.get("solver.iterations")
    if not iterations or "solver.linesearch_trials" not in counters:
        return None
    return counters["solver.linesearch_trials"] / iterations
