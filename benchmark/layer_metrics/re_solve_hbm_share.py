"""Layer: coordinate descent. The bytes an average random-effect update's
solves must move (benchmark/lib/re_bytes.py: the buckets' block bytes × two
passes × the lock-step iterations the program counted, + the two passes at
the warm start) over ``re_solve_ms``, as a share of the device's peak HBM
bandwidth. The solves are XLA's: this is their roofline share."""
from benchmark.layer_metrics import re_solve_ms
from benchmark.lib.re_bytes import update_bytes


def read(ctx):
    ms = re_solve_ms.read(ctx)
    counters = ctx["telemetry"]["counters"]
    blocks = ctx["state"].facts.get("blocks")
    n_blocks = counters.get("game_re.blocks")
    if (ms is None or ctx["peaks"] is None or not blocks or not n_blocks
            or "game_re.block_steps" not in counters):
        return None
    steps = counters["game_re.block_steps"] / n_blocks
    moved = update_bytes(blocks, steps)
    return 100.0 * moved / (ms / 1e3) / ctx["peaks"]["hbm_bytes_per_s"]
