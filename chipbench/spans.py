"""The program's own host spans in a traced run's profile.

The program names its serving phases with `jax.profiler.TraceAnnotation`
spans: ``dse.*``, and ``py.gc`` for the collector (see
``src/repro/utils/trace.py``).  ``tracing.reduce`` keeps only the
benchmark's own ``bench.*`` spans, so this module reads the program's
from the same ``.xplane.pb``:

- ``read_host``: the traced window (``bench.window``) and every program
  span, as (name, (start, end), batch id or None);
- ``run_spans``: the program spans of the traced run a reader's context
  describes, found among the cell's profiles by the window's exact bounds;
- ``attribute``: idle gaps named after the program span overlapping each
  most, the shorter on a tie so that the innermost of nested spans wins,
  else after the benchmark's span as ``tracing.attribute`` names it;
- ``idle_by_phase``: idle time split among the innermost spans over it.

    python3 -m chipbench.spans <trace dir or .xplane.pb>

prints the device's longest idle gaps so named, the idle time of each
phase (``idle_by_phase``), and each program span's count and mean
duration in the window.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import harness, tracing

PREFIXES = ("dse.", "py.")
Span = Tuple[str, tracing.Interval, Optional[int]]


def read_host(path: str) -> Tuple[Optional[tracing.Interval], List[Span]]:
    """(window, program spans) of one profile."""
    from jax.profiler import ProfileData
    window, out = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s = int(e.start_ns)
                iv = (s, s + int(e.duration_ns))
                if e.name.startswith(PREFIXES):
                    out.append((e.name, iv, dict(e.stats).get("batch")))
                elif e.name == tracing.WINDOW_SPAN and window is None:
                    window = iv
    return window, out


def run_spans(ctx: dict) -> Optional[List[Span]]:
    """The program spans of the run whose reduced trace is ctx["trace"]:
    of the cell's profiles under results/trace, newest first, the one
    whose traced window has the same bounds.  None where there is none."""
    tr, cell = ctx.get("trace"), ctx.get("cell")
    if not tr or not cell:
        return None
    pattern = os.path.join(harness.RESULTS_DIR, "trace",
                           glob.escape(cell["name"]) + "-*", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        window, spans = read_host(path)
        if window == tuple(tr["window"]):
            return spans
    return None


def in_window(spans: Sequence[Span], window: tracing.Interval,
              name: str) -> List[int]:
    """Durations (ns) of the spans ``name`` whose midpoint lies in
    ``window``."""
    lo, hi = window
    return [e - s for n, (s, e), _ in spans
            if n == name and lo <= (s + e) // 2 < hi]


class Index:
    """Spans (name, interval) sorted by start, for finding those that
    overlap a gap without a pass over all of them."""

    def __init__(self, spans: Sequence[Tuple[str, tracing.Interval]]):
        self.spans = sorted(spans, key=lambda x: x[1][0])
        self.starts = [iv[0] for _, iv in self.spans]
        self.longest = max((e - s for _, (s, e) in self.spans), default=0)

    def over(self, gap: tracing.Interval
             ) -> List[Tuple[str, tracing.Interval]]:
        lo = bisect.bisect_left(self.starts, gap[0] - self.longest)
        hi = bisect.bisect_left(self.starts, gap[1])
        return [(n, iv) for n, iv in self.spans[lo:hi] if iv[1] > gap[0]]


def innermost(gap: tracing.Interval,
              spans: Sequence[Tuple[str, tracing.Interval]]) -> Optional[str]:
    """Name of the span overlapping ``gap`` most, the shorter on a tie."""
    best, name = None, None
    for n, (s, e) in spans:
        o = min(gap[1], e) - max(gap[0], s)
        if o > 0 and (best is None or (o, s - e) > best):
            best, name = (o, s - e), n
    return name


def attribute(gap_list: Sequence[tracing.Interval],
              program: Sequence[Tuple[str, tracing.Interval]],
              bench: Sequence[Tuple[str, tracing.Interval]]
              ) -> List[Tuple[str, float]]:
    """Each gap -> (name, seconds): the innermost program span over it,
    else the benchmark's span (``tracing.attribute``), else "none"."""
    prog, other = Index(program), Index(bench)
    return [(innermost(g, prog.over(g))
             or tracing.attribute([g], other.over(g))[0][0],
             (g[1] - g[0]) * 1e-9) for g in gap_list]


def idle_by_phase(gap_list: Sequence[tracing.Interval],
                  program: Sequence[Tuple[str, tracing.Interval]]
                  ) -> Dict[str, float]:
    """Idle seconds under each program span name: every stretch of a gap
    goes to the shortest program span that covers it ("none" where none
    does), so a gap across a parent and its children is split among
    them."""
    index = Index(program)
    out: Dict[str, float] = collections.defaultdict(float)
    for g0, g1 in gap_list:
        over = [(e - s, n, max(s, g0), min(e, g1))
                for n, (s, e) in index.over((g0, g1))]
        cuts = sorted({g0, g1, *(a for *_, a, _ in over),
                       *(b for *_, b in over)})
        for a, b in zip(cuts, cuts[1:]):
            cover = [(d, n) for d, n, s, e in over if s <= a and b <= e]
            out[min(cover)[1] if cover else "none"] += (b - a) * 1e-9
    return dict(sorted(out.items(), key=lambda x: -x[1]))


def breakdown(path: str, k: int = 10) -> Optional[dict]:
    """Device 0's idle time in the traced window: the ``k`` longest gaps
    named by ``attribute``, and the idle seconds of each phase
    (``idle_by_phase``); each program span's count and mean milliseconds
    (midpoint in the window)."""
    tr = tracing.Trace(path)
    win = tr.window()
    if win is None or not tr.ops:
        return None
    _, program = read_host(path)
    lo, hi = win
    busy0 = tracing.union(tracing.clip(
        [(s, e) for _, s, e in tr.ops[sorted(tr.ops)[0]]], lo, hi))
    gap_list = tracing.gaps(busy0, lo, hi)
    named = [(n, iv) for n, iv, _ in program]
    idle_gaps = attribute(gap_list, named,
                          [(n, iv) for n, iv in tr.host
                           if n != tracing.WINDOW_SPAN])
    spans = {}
    for n in sorted({n for n, _ in named}):
        d = in_window(program, win, n)
        if d:
            spans[n] = {"count": len(d), "mean_ms": 1e-6 * sum(d) / len(d)}
    return {"window_s": (hi - lo) * 1e-9,
            "idle_s": sum(g[1] - g[0] for g in gap_list) * 1e-9,
            "idle_gaps": sorted(idle_gaps, key=lambda x: -x[1])[:k],
            "idle_s_by_phase": idle_by_phase(gap_list, named),
            "spans": spans}


if __name__ == "__main__":
    import sys
    arg = sys.argv[1]
    print(json.dumps(breakdown(arg if arg.endswith(".pb")
                               else tracing.find_xplane(arg)), indent=1))
