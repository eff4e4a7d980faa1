"""Layer: mesh. Padded ÷ real bytes of the sharded layout's per-shard ELL
and occurrence buckets: the program's counters
``layout.shard_bytes_padded`` ÷ ``layout.shard_bytes_real``, emitted at the
sharded build and kept from set-up — what padding every shard to the
common shapes (r_b the most rows any shard has at a width, a column's
bucket from its max-local count) costs over each shard laid out alone."""


def read(ctx):
    counters = ctx["state"].facts.get("build_counters", {})
    real = counters.get("layout.shard_bytes_real")
    if not real or "layout.shard_bytes_padded" not in counters:
        return None
    return counters["layout.shard_bytes_padded"] / real
