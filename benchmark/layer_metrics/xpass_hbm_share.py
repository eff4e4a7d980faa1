"""Layer: X pass. The bytes one evaluation must move (benchmark/lib/
xpass_bytes.py, from the layout's own shapes) over the evaluation's device
time, as a share of the device's peak HBM bandwidth."""
from benchmark.layer_metrics import xpass_eval_ms


def read(ctx):
    ms = xpass_eval_ms.read(ctx)
    if ms is None or ctx["peaks"] is None:
        return None
    moved = ctx["state"].facts["xpass_bytes"]["total"]
    return 100.0 * moved / (ms / 1e3) / ctx["peaks"]["hbm_bytes_per_s"]
