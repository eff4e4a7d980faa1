"""Layer: X pass. Device busy time of the traced ``xpass`` section (bare
value-and-gradient evaluations at a fixed w) per evaluation."""


def read(ctx):
    section = ctx["trace"]["sections"].get("xpass")
    if section is None:
        return None
    n = sum(r["evaluations"] for r in ctx["results"]["xpass"])
    return section["busy_s"] / n * 1e3
