"""The trace reduction: interval arithmetic on made-up intervals, and the
whole reduction on a small trace recorded on a v5e
(tests/record_trace.py: five jit_probe calls, each followed by 20 ms of
host sleep in a bench.wait span, inside bench.window)."""
import os

import pytest

from chipbench import tracing

PROBE = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")


def test_union_clip_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (39, 41), (50, 60)]
    u = tracing.union(iv)
    assert u == [(0, 20), (30, 41), (50, 60)]
    c = tracing.clip(u, 10, 55)
    assert c == [(10, 20), (30, 41), (50, 55)]
    assert tracing.gaps(c, 10, 70) == [(20, 30), (41, 50), (55, 70)]
    names = tracing.attribute([(20, 30), (41, 50)],
                              [("bench.wait", (18, 29)), ("bench.x", (40, 60))])
    assert [n for n, _ in names] == ["bench.wait", "bench.x"]
    assert [s for _, s in names] == pytest.approx([10e-9, 9e-9])


def test_recorded_chip_trace():
    red = tracing.reduce(PROBE)
    assert red is not None and red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    probe = {n: v for n, v in red["modules"].items()
             if n.startswith("jit_probe")}
    # each call is about 0.1 ms of device time, so the first one can fall
    # just before the window's host span on the device's clock
    assert 4 <= sum(v[1] for v in probe.values()) <= 5
    # five host sleeps of 20 ms: the five longest gaps are the waits
    waits = [g for g in red["idle_gaps"] if g[0] == "bench.wait"]
    assert len(waits) >= 5
    assert all(0.019 < s < 0.05 for _, s in waits[:5])
    assert red["window_s"] > 0.1


def _reader(name):
    from chipbench import harness
    return harness.reader(name)


def test_programs_read_over_the_batches_of_the_window():
    """A program's time and its batch's work are counted over the same
    batches: an event whose midpoint lies outside the window goes out with
    its batch's candidates, also where its batch's span crosses the edge."""
    spans = {0: (-50, 40), 1: (40, 120), 2: (120, 200), 3: (200, 280),
             4: (280, 360)}
    runs = [("jit_run(a)", -40, 30), ("jit_run(a)", 45, 115),
            ("jit_run(a)", 125, 195), ("jit_run(a)", 205, 275),
            ("jit_run(a)", 285, 355)]
    fwds = [("jit_fwd(b)", 41, 44), ("jit_fwd(b)", 121, 123)]
    tr = {"window": (0, 300), "window_s": 300e-9, "devices": 1,
          "batch_spans": spans, "module_events": runs + fwds}
    batches = [{"rows": 64, "candidates": 10 * (k + 1)} for k in range(5)]
    ctx = {"trace": tr, "batches": batches,
           "g_shapes": [(19, 8), (8, 29)],
           "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}
    # batches 1-3: 3 x 70 ns of select over 20 + 30 + 40 candidates
    assert _reader("select_ns_per_cand")(ctx) == pytest.approx(210 / 90)
    from chipbench import work
    least = work.least_time(*work.mlp_forward(64, ctx["g_shapes"]),
                            ctx["peak"])[0]
    assert _reader("gfwd_roofline")(ctx) == pytest.approx(
        100 * 2 * least / 5e-9)
    flops = work.mlp_forward(64, ctx["g_shapes"])[0]
    assert _reader("mfu.serve")(ctx) == pytest.approx(
        100 * 2 * flops / (300e-9 * 1e12))
    # no batch spans (an untraced program): the readers return nothing
    ctx["trace"] = dict(tr, batch_spans={})
    assert _reader("select_ns_per_cand")(ctx) is None
    assert _reader("gfwd_roofline")(ctx) is None
