"""Algorithm 1's model operations per step (work.alg1_step_flops) times the
steps of the traced window, over the window and the chips' bf16 peak."""
from chipbench import work


def read(ctx):
    if not ctx.get("steps") or not ctx.get("window_s"):
        return None
    flops = work.alg1_step_flops(ctx["batch"], ctx["g_shapes"],
                                 ctx["d_shapes"]) * ctx["steps"]
    chips = ctx["cell"]["chips"]
    return 100.0 * flops / (ctx["window_s"] * chips
                            * ctx["peak"]["bf16_flops_per_s"])
