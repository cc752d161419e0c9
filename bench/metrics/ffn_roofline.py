"""ffn_roofline: the least time of the step's FFN matmuls (6 FLOPs per
FFN matmul parameter and token, every layer, at the bf16 peak) over the
device time of the ops in the program's ``ffn`` scope, forward and
backward (norm, activation and residual add included), in %. Nothing when
no op carries the scope."""
from bench import layer_work, scopes


def read(ctx):
    secs = scopes.cell_scope_seconds(ctx)
    spent = secs.get("ffn.fwd", 0.0) + secs.get("ffn.bwd", 0.0)
    if spent <= 0.0:
        return None
    mix = ctx["mix"]
    least = layer_work.ffn_roofline_s(ctx["config"], mix["batch"],
                                      mix["seq"], ctx["peak"]) * ctx["steps"]
    return 100.0 * least / spent
