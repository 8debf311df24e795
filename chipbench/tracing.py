"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

- busy: the union of the intervals in which an operation ran on a device
  (the device plane's "XLA Ops" line), clipped to the traced window and
  averaged over the devices;
- time by name: summed device durations of each program ("XLA Modules"
  line) and of each operation ("XLA Ops" line);
- idle gaps: the longest stretches with no operation on device 0, each
  named after the benchmark's own host span (``bench.*``) that overlaps it
  most, or "none";
- per batch: the host span ``bench.batch.<k>`` around the engine call of
  the served batch k, so that a reader can give each program event the
  batch whose span holds it (see metrics_common.per_batch).

The traced window is the benchmark's own host span ``bench.window``.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
BATCH_SPAN = "bench.batch."
Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute(gap_list: List[Interval], spans: List[Tuple[str, Interval]]
              ) -> List[Tuple[str, float]]:
    """Each gap -> (name of the host span overlapping it most, seconds)."""
    out = []
    for g in gap_list:
        best, name = 0, "none"
        for n, iv in spans:
            o = _overlap(g, iv)
            if o > best:
                best, name = o, n
        out.append((name, (g[1] - g[0]) * 1e-9))
    return out


class Trace:
    """Events of one trace, as plain tuples."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        self.ops: Dict[str, List[Tuple[str, int, int]]] = {}
        self.modules: Dict[str, List[Tuple[str, int, int]]] = {}
        self.host: List[Tuple[str, Interval]] = []
        self.batches: Dict[int, Interval] = {}
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:") and \
                    plane.name[len("/device:TPU:"):].isdigit():
                for line in plane.lines:
                    ev = [(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                          for e in line.events]
                    if line.name == "XLA Ops":
                        self.ops[plane.name] = ev
                    elif line.name == "XLA Modules":
                        self.modules[plane.name] = ev
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            s = int(e.start_ns)
                            iv = (s, s + int(e.duration_ns))
                            name = e.name
                            if name.startswith(BATCH_SPAN):
                                self.batches[int(name[len(BATCH_SPAN):])] = iv
                                name = BATCH_SPAN[:-1]
                            self.host.append((name, iv))

    def window(self) -> Optional[Interval]:
        spans = [iv for n, iv in self.host if n == WINDOW_SPAN]
        return spans[0] if spans else None


def reduce(path: str) -> Optional[dict]:
    """The numbers the per-layer readers take, or None when the trace has
    no device operation inside the window."""
    tr = Trace(path)
    win = tr.window()
    if win is None or not tr.ops:
        return None
    lo, hi = win
    busy_s = []
    for ev in tr.ops.values():
        busy_s.append(sum(e - s for s, e in union(
            clip([(s, e) for _, s, e in ev], lo, hi))) * 1e-9)
    if not any(busy_s):
        return None

    def by_name(table) -> Dict[str, List[float]]:
        out: Dict[str, List[float]] = collections.defaultdict(
            lambda: [0.0, 0])
        for ev in table.values():
            for name, s, e in ev:
                if e > lo and s < hi:
                    out[name][0] += (e - s) * 1e-9
                    out[name][1] += 1
        return {k: v for k, v in out.items()}

    first = sorted(tr.ops)[0]
    busy0 = union(clip([(s, e) for _, s, e in tr.ops[first]], lo, hi))
    spans = [(n, iv) for n, iv in tr.host if n != WINDOW_SPAN]
    idle = sorted(attribute(gaps(busy0, lo, hi), spans),
                  key=lambda x: -x[1])[:10]
    n_dev = len(tr.ops)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_s) / n_dev,
        "devices": n_dev,
        "ops": by_name(tr.ops),            # name -> [seconds, count]
        "modules": by_name(tr.modules),
        "module_events": [ev for evs in tr.modules.values() for ev in evs],
        "window": (lo, hi),
        "batch_spans": tr.batches,
        "idle_gaps": idle,
    }


def top(table: Dict[str, List[float]], k: int = 10) -> List[list]:
    """The k names of most time, each cut to its first 120 characters
    (an XLA op's name is its whole HLO line)."""
    return [[n[:120], v[0]] for n, v in sorted(table.items(),
                                               key=lambda x: -x[1][0])[:k]]


def describe(path: str, k: int = 25) -> None:
    """Print a trace's planes, lines and most frequent event names: the
    look by hand that the readers' program names come from."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            ev = list(line.events)
            if not ev:
                continue
            tot = collections.Counter()
            cnt = collections.Counter()
            for e in ev:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
            print(f"  line {line.name!r}: {len(ev)} events, "
                  f"ns {int(ev[0].start_ns)}..{int(ev[-1].start_ns)}")
            for n, t in tot.most_common(k if plane.name.startswith(
                    "/device") else 5):
                print(f"    {t * 1e-9:.6f} s  x{cnt[n]}  {n[:160]}")


if __name__ == "__main__":
    import sys
    path = sys.argv[1]
    describe(path if path.endswith(".pb") else find_xplane(path))
    red = reduce(path if path.endswith(".pb") else find_xplane(path))
    if red:
        print({k: v for k, v in red.items()
               if k not in ("ops", "modules", "module_events", "batch_spans")})
