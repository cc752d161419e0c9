"""flash_attn_roofline: the least time of the step's attention, forward
and backward, over the device time of the flash kernels (flash_fwd,
flash_bwd_dq, flash_bwd_dkv), in %. Nothing when no flash kernel ran."""
from bench import work

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(ctx):
    tr, cfg, mix = ctx["trace"], ctx["config"], ctx["mix"]
    spent = sum(tr.kernel_s(k) for k in KERNELS) / tr.chips
    if spent <= 0.0:
        return None
    least = work.attention_roofline_s(cfg, mix["batch"], mix["seq"],
                                      ctx["peak"]) * ctx["steps"]
    return 100.0 * least / spent
