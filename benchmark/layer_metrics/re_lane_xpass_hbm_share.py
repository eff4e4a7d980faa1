"""Layer: coordinate descent. The bytes an average random-effect update of
a regularization grid moves in its block passes, all its lanes sharing
each bucket's block (benchmark/lib/re_lane_bytes.py, G from the program's
counter ``game_grid.lanes``, the lock-step iterations from
``game_re.block_steps`` ÷ ``game_re.blocks``), over the device time of the
``xpass.*`` scopes under ``game_re.solve`` (``re_xpass_ms``, which is per
lane of an update), as a share of the device's peak HBM bandwidth: the
lane solves' passes' roofline share. None for a program with no lane grid
(no ``game_grid.lanes``)."""
from benchmark.layer_metrics import re_xpass_ms
from benchmark.lib.re_lane_bytes import update_bytes


def read(ctx):
    counters = ctx["telemetry"]["counters"]
    lanes = counters.get("game_grid.lanes")
    n_units = len(ctx["results"].get("unit", []))
    blocks = ctx["state"].facts.get("blocks")
    n_blocks = counters.get("game_re.blocks")
    if (not lanes or not n_units or ctx["peaks"] is None or not blocks
            or not n_blocks or "game_re.block_steps" not in counters):
        return None
    ms = re_xpass_ms.read(ctx)
    if ms is None:
        return None
    lanes = lanes / n_units  # the counter is G once a fit
    steps = counters["game_re.block_steps"] / n_blocks
    moved = update_bytes(blocks, steps, int(round(lanes))) / lanes
    return 100.0 * moved / (ms / 1e3) / ctx["peaks"]["hbm_bytes_per_s"]
