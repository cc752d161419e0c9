"""step_mfu: the whole step's model FLOP/s over the chips' bf16 peak, in %.

Model FLOPs per step come from work.model_flops_per_step (no
recomputation counted); the time is the traced window on the host clock,
so the profiler's own cost (2-3.5% of a step on a v5e) is in it: the
untraced window's rate is train_tokens_per_s.
"""
from bench import work


def read(ctx):
    cfg, mix = ctx["config"], ctx["mix"]
    flops = work.model_flops_per_step(cfg, mix["batch"], mix["seq"])
    rate = flops * ctx["steps"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
