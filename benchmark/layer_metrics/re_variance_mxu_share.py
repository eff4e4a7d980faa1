"""Layer: coordinate descent. The floating-point operations an average
random-effect update's FULL variances must do (benchmark/lib/
variance_flops.py, from the buckets' padded shapes) over
``re_variance_ms``, as a share of the device's peak bf16 rate: the
variance kernel's roofline share. The kernel runs its products at HIGHEST
precision, several MXU passes a product, so its own ceiling is a fraction
of this peak."""
from benchmark.layer_metrics import re_variance_ms
from benchmark.lib.variance_flops import update_flops


def read(ctx):
    ms = re_variance_ms.read(ctx)
    blocks = ctx["state"].facts.get("blocks")
    if ms is None or ctx["peaks"] is None or not blocks:
        return None
    return (100.0 * update_flops(blocks) / (ms / 1e3)
            / ctx["peaks"]["bf16_flops_per_s"])
