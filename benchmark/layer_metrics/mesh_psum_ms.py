"""Layer: mesh. Device self time inside the traced whole solves under the
scope ``mesh.psum`` (the objective's all-reduces over the mesh axis: the
(value, gradient) pair an evaluation, two scalars a line-search trial),
per lock-step solver iteration, averaged over the device planes — what of
the collective sits on the devices' op line. A program without the scope
reports nothing."""
from benchmark.lib.scope_reduce import scope_ms_per_iteration, unit_scopes

SCOPE = "mesh.psum"


def read(ctx):
    table = unit_scopes()
    if table is None or SCOPE not in table["scopes"]:
        return None
    return scope_ms_per_iteration(ctx, lambda chain: chain[-1] == SCOPE)
