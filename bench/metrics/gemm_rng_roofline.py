"""gemm_rng_roofline: the least time of the step's attention
out-projections, the GEMMs that host the next layer's dropout bits under
the ``prev_gemm`` site, over the device time of the gemm_rng kernel, in
%. The bits are not counted as work: if the RNG is hidden under the MXU
work this reads like a plain GEMM. Nothing when gemm_rng did not run."""
from bench import work


def read(ctx):
    tr, cfg, mix = ctx["trace"], ctx["config"], ctx["mix"]
    spent = tr.kernel_s("gemm_rng") / tr.chips
    if spent <= 0.0:
        return None
    least = work.out_proj_roofline_s(
        cfg, mix["batch"], mix["seq"], mix["dropout"]["host_dtype"],
        ctx["peak"]) * ctx["steps"]
    return 100.0 * least / spent
