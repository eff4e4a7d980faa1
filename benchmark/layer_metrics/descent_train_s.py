"""Layer: coordinate descent. The driver's ``train.train`` span, per
traced fit."""
from benchmark.lib.harness import span_seconds_per_unit


def read(ctx):
    return span_seconds_per_unit(ctx, "train.train")
