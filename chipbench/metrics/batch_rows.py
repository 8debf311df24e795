"""Real rows per dispatched micro-batch over the window (server counters)."""


def read(ctx):
    s = ctx.get("stats") or {}
    return s["dispatched_rows"] / s["batches"] if s.get("batches") else None
