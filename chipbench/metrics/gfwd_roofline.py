"""G forward's share of its roofline: the least time of the forward's work
(work.mlp_forward at each batch's padded rows) over the device time of the
jitted forward program, over the batches the traced window holds."""
from chipbench import work
from chipbench.metrics_common import per_batch

PROGRAM = "jit_fwd"


def read(ctx):
    got = per_batch(ctx, PROGRAM)
    t = sum(sec for sec, _ in got.values())
    if not t:
        return None
    least = sum(n * work.least_time(*work.mlp_forward(
        ctx["batches"][k]["rows"], ctx["g_shapes"]), ctx["peak"])[0]
        for k, (_, n) in got.items())
    return 100.0 * least / t
