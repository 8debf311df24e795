"""Helpers the per-layer readers share."""
from __future__ import annotations

from typing import Dict, List


def per_batch(ctx, program: str) -> Dict[int, List[float]]:
    """Device events of ``program`` (the trace names it ``program`` or
    ``program(...)``) by the served batch they belong to: the batch whose
    host span ``bench.batch.<k>`` holds the event's midpoint, for events
    whose midpoint lies in the traced window.  -> {k: [device seconds,
    events]}, summed over the devices.  A program's time and the batch's
    work (ctx["batches"][k]) are so read over the same batches, whatever
    the window's edges cut."""
    tr = ctx.get("trace")
    if not tr or not tr.get("batch_spans"):
        return {}
    lo, hi = tr["window"]
    spans = sorted((s, e, k) for k, (s, e) in tr["batch_spans"].items())
    out: Dict[int, List[float]] = {}
    for name, s, e in tr["module_events"]:
        if not (name == program or name.startswith(program + "(")):
            continue
        mid = (s + e) // 2
        if not lo <= mid < hi:
            continue
        for bs, be, k in spans:
            if bs <= mid < be:
                acc = out.setdefault(k, [0.0, 0])
                acc[0] += (e - s) * 1e-9
                acc[1] += 1
                break
    n = len(ctx.get("batches") or [])
    return {k: v for k, v in out.items() if k < n}
