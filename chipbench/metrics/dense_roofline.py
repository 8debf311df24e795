"""fused_dense kernels' share of their roofline in the train step: the
summed least time of the calls one Algorithm 1 step needs
(work.alg1_dense_calls: 82 at the paper's depth, the count the compiled
epoch holds), times the traced steps, over the summed device time of the
kernels' events.  In the trace each kernel call is an XLA op whose name
is its HLO line, ``%jvp_jit_fused_dense__.N = ... custom-call(...)``
forward and ``%transpose_jvp_jit_fused_dense___.N = ...`` backward."""
from chipbench import work


def is_kernel(name: str) -> bool:
    return "fused_dense" in name and " custom-call(" in name


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("steps"):
        return None
    t = sum(sec for name, (sec, _) in tr["ops"].items()
            if is_kernel(name)) / tr["devices"]
    if not t:
        return None
    calls = work.alg1_dense_calls(ctx["batch"], ctx["g_shapes"],
                                  ctx["d_shapes"])
    least = sum(work.least_time(*work.KERNELS[kind](*shape), ctx["peak"])[0]
                for kind, shapes in calls.items() for shape in shapes)
    return 100.0 * least * ctx["steps"] / t
