"""Host time per batch inside the engine call (the server's dispatch_s
over its batches): G forward, fused select and the float64 host tail."""


def read(ctx):
    s = ctx.get("stats") or {}
    return 1e3 * s["dispatch_s"] / s["batches"] if s.get("batches") else None
