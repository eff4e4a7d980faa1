"""Layer: coordinate descent. Padded ÷ real block bytes of the bucket plans
the fit's datasets were built to: the program's counters
``game_re.block_bytes_padded`` ÷ ``game_re.block_bytes_real``, summed over
the random-effect coordinates at their build (in the warm-up fit)."""


def read(ctx):
    counters = ctx["state"].facts.get("build_counters", {})
    real = counters.get("game_re.block_bytes_real")
    if not real or "game_re.block_bytes_padded" not in counters:
        return None
    return counters["game_re.block_bytes_padded"] / real
