"""Layer: stream. What ONE chunk of a pass costs the chip, everything
counted: device busy time of the traced whole solves ÷ the chunks their
passes consumed (the program's counter ``stream.chunk_uploads``, one chunk
program a chunk). Three things are in the numerator: the chunk PROGRAM (the
X pass and the loss: 53.5 of 86.3 ms at the cell's first reading, the part
to set against a resident shard's iteration, which is two such programs;
`solve_xpass_ms` × iterations ÷ chunks reads it alone), the chunk's
ASSEMBLY on the device (the upload's zero-fill and its in-place writes of
row pieces: 29.7 ms), and the solver's own programs between passes
(direction, history push, margin-cached trials: a few per cent)."""


def read(ctx):
    chunks = ctx["telemetry"]["counters"].get("stream.chunk_uploads")
    unit = ctx["trace"]["sections"].get("unit")
    if not chunks or not unit:
        return None
    return unit["busy_s"] / chunks * 1e3
