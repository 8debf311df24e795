"""Mean host time of a batch's tail: the program's ``dse.host_tail`` span
(the float64 rescoring of the winners and the Selections built from
them), over the spans whose midpoint lies in the traced window.  The
spans are read from the run's profile (chipbench/spans.py); a program
without the span reads nothing."""
from chipbench import spans

SPAN = "dse.host_tail"


def read(ctx):
    got = spans.run_spans(ctx)
    d = spans.in_window(got, ctx["trace"]["window"], SPAN) if got else []
    return 1e-6 * sum(d) / len(d) if d else None
