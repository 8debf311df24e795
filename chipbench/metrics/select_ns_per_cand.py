"""Device time of the fused select program per candidate it scanned: the
select's events of the batches the traced window holds, over the
candidates of those batches' rows (padding rows included)."""
from chipbench.metrics_common import per_batch

PROGRAM = "jit_run"


def read(ctx):
    got = per_batch(ctx, PROGRAM)
    cands = sum(ctx["batches"][k]["candidates"] for k in got)
    t = sum(sec for sec, _ in got.values())
    if not t or not cands:
        return None
    return 1e9 * t / cands
