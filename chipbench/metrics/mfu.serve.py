"""The served path's share of the chip's peak: the model operations of G's
forwards in the traced window (work.mlp_forward at each batch's padded
rows), over the window and the bf16 peak of the chips.  Beside the G
forward's roofline it bounds what taking a kernel off the path can claim;
the select's decode and oracle are not counted as model operations."""
from chipbench import work
from chipbench.metrics_common import per_batch

PROGRAM = "jit_fwd"


def read(ctx):
    tr = ctx.get("trace")
    got = per_batch(ctx, PROGRAM)
    if not tr or not got:
        return None
    d = tr["devices"]
    flops = sum(n / d * work.mlp_forward(ctx["batches"][k]["rows"],
                                         ctx["g_shapes"])[0]
                for k, (_, n) in got.items())
    return 100.0 * flops / (tr["window_s"] * d
                            * ctx["peak"]["bf16_flops_per_s"])
