"""optimizer_roofline: the least time of the step's AdamW update (24 bytes
per parameter: f32 master, m and v, each read and written once, over the
HBM bandwidth; the gradients are not counted) over the device time of the
ops in the program's ``optimizer`` scope (clipping and AdamW), in %.
Nothing when no op carries the scope."""
from bench import layer_work, scopes


def read(ctx):
    spent = scopes.cell_scope_seconds(ctx).get("optimizer.fwd", 0.0)
    if spent <= 0.0:
        return None
    n = layer_work.param_count(ctx["config"], ctx["mix"])
    least = layer_work.optimizer_roofline_s(n, ctx["peak"]) * ctx["steps"]
    return 100.0 * least / spent
