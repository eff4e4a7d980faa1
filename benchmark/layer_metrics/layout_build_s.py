"""Layer: layout. Host clock around `to_blocked_ell` + `device_put` +
`block_until_ready` in set-up (hot block built on the device)."""


def read(ctx):
    return ctx["state"].clocks.get("layout_build_s")
