"""Layer: coordinate descent. Coordinate updates plus random-effect blocks
dispatched, per traced fit (`game.coordinate_updates` + `game_re.blocks`)."""


def read(ctx):
    counters = ctx["telemetry"]["counters"]
    n = len(ctx["results"].get("unit", []))
    if not n or "game.coordinate_updates" not in counters:
        return None
    return (counters["game.coordinate_updates"]
            + counters.get("game_re.blocks", 0.0)) / n
