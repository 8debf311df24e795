"""Share of the scanned candidates that the design model's oracle found
feasible (finite latency and power), in percent: 100 x the candidates
found feasible over those scanned, summed over the program's
``dse.host_tail`` spans whose midpoint lies in the traced window.  The
fused select hangs the two counts of its call on that span as metadata
(``select_feasible``, ``select_scanned``).  ``spans.read_host`` keeps only
a span's batch id, so this reader reads the run's profile itself, found
as ``spans.run_spans`` finds it: by the traced window's exact bounds.  A
program whose span carries no counts reads nothing."""
import glob
import os

from chipbench import harness, tracing

SPAN = "dse.host_tail"


def counts(path: str):
    """(traced window, [(midpoint, scanned, feasible)]) of one profile."""
    from jax.profiler import ProfileData
    window, out = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s = int(e.start_ns)
                if e.name == SPAN:
                    st = dict(e.stats)
                    if "select_scanned" in st and "select_feasible" in st:
                        out.append((s + int(e.duration_ns) // 2,
                                    int(st["select_scanned"]),
                                    int(st["select_feasible"])))
                elif e.name == tracing.WINDOW_SPAN and window is None:
                    window = (s, s + int(e.duration_ns))
    return window, out


def read(ctx):
    tr, cell = ctx.get("trace"), ctx.get("cell")
    if not tr or not cell:
        return None
    pattern = os.path.join(harness.RESULTS_DIR, "trace",
                           glob.escape(cell["name"]) + "-*", "**",
                           "*.xplane.pb")
    lo, hi = tr["window"]
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        window, got = counts(path)
        if window != (lo, hi):
            continue
        inside = [(n, f) for mid, n, f in got if lo <= mid < hi]
        scanned = sum(n for n, _ in inside)
        return 100.0 * sum(f for _, f in inside) / scanned if scanned \
            else None
    return None
