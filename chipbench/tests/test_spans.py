"""The program's own spans in a profile (chipbench/spans.py) and the
reader built on them: innermost-span naming of idle gaps, finding a run's
profile by its window, and ``host_tail_ms`` on a profile recorded here;
the recorded chip trace still reduces to the numbers it gave before."""
import os
import time

import jax
import pytest

from chipbench import harness, spans, tracing

PROBE = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")


def test_gaps_go_to_the_innermost_program_span():
    bench = [("bench.batch", (0, 100)), ("bench.wait", (100, 200))]
    program = [("dse.execute", (0, 90)), ("dse.host_tail", (60, 90)),
               ("dse.sync", (20, 40)), ("py.gc", (65, 70))]
    gaps = [(62, 88), (25, 35), (10, 15), (91, 99), (150, 160), (300, 310),
            (64, 80), (65, 70)]
    assert [n for n, _ in spans.attribute(gaps, program, bench)] == [
        "dse.host_tail",   # inside host_tail and execute: the shorter
        "dse.sync",
        "dse.execute",     # inside execute alone
        "bench.batch",     # no program span: the benchmark's, as before
        "bench.wait",
        "none",
        "dse.host_tail",   # overlaps host_tail most, py.gc less
        "py.gc"]           # as much as the others: the shortest
    assert [s for _, s in spans.attribute(gaps[:2], program, bench)] == \
        pytest.approx([26e-9, 10e-9])


def test_idle_split_among_innermost_phases():
    """A gap across a parent and its children is split among them: each
    stretch goes to the shortest span over it."""
    program = [("dse.execute", (0, 100)), ("dse.gfwd", (10, 40)),
               ("dse.host_tail", (60, 90)), ("py.gc", (70, 80)),
               ("dse.form", (95, 130))]
    got = spans.idle_by_phase([(0, 120), (150, 160)], program)
    assert got == pytest.approx({
        "dse.execute": 35e-9,     # 0-10, 40-60, 90-95
        "dse.gfwd": 30e-9, "dse.host_tail": 20e-9, "py.gc": 10e-9,
        "dse.form": 25e-9,        # 95-120: shorter than execute's 100
        "none": 10e-9})
    assert list(got)[0] == "dse.execute"


def test_recorded_chip_trace_reduces_as_before():
    red = tracing.reduce(PROBE)
    assert red["busy_s"] == pytest.approx(0.000377869, rel=1e-12)
    assert red["window_s"] == pytest.approx(0.109118666, rel=1e-12)
    assert [n for n, _ in red["idle_gaps"]] == ["bench.wait"] * 10
    assert [s for _, s in red["idle_gaps"][:5]] == pytest.approx(
        [0.023012531, 0.021798165, 0.021738171, 0.021516736, 0.020675155],
        rel=1e-12)
    assert harness.reader("idle_share.serve")({"trace": red}) == \
        pytest.approx(99.65370819324349, rel=1e-12)
    # no program span in it: the breakdown names the same gaps
    out = spans.breakdown(PROBE)
    assert out["spans"] == {} and [n for n, _ in out["idle_gaps"]] == \
        ["bench.wait"] * 10
    assert out["idle_s_by_phase"] == {"none": pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)}


def _record(tdir, program: bool):
    """A profile of a bench.window holding, where ``program``, one batch's
    dse.execute > dse.host_tail (about 20 ms) and a dse.host_tail that
    starts after the window closes."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            if program:
                with jax.profiler.TraceAnnotation("dse.execute", batch=4):
                    with jax.profiler.TraceAnnotation("dse.host_tail",
                                                      batch=4):
                        time.sleep(0.02)
            time.sleep(0.005)
        if program:
            with jax.profiler.TraceAnnotation("dse.host_tail", batch=5):
                time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    return tracing.find_xplane(str(tdir))


def test_host_tail_read_from_the_runs_profile(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "RESULTS_DIR", str(tmp_path))
    cell = {"name": "dnnweaver-interactive"}
    older = _record(tmp_path / "trace" / "dnnweaver-interactive-1", True)
    path = _record(tmp_path / "trace" / "dnnweaver-interactive-2", True)
    window, got = spans.read_host(path)
    assert window == tracing.Trace(path).window()
    assert sorted((n, b) for n, _, b in got) == [
        ("dse.execute", 4), ("dse.host_tail", 4), ("dse.host_tail", 5)]
    read = harness.reader("host_tail_ms")
    ms = read({"trace": {"window": window}, "cell": cell})
    assert 20 <= ms < 45            # the one span inside the window
    # the older run's profile is found by its window, not by its age
    w_old = tracing.Trace(older).window()
    assert spans.run_spans({"trace": {"window": w_old}, "cell": cell}) == \
        spans.read_host(older)[1]
    # no such window, no trace, another cell: nothing to read
    assert read({"trace": {"window": (0, 1)}, "cell": cell}) is None
    assert read({"trace": None, "cell": cell}) is None
    assert read({"trace": {"window": window},
                 "cell": {"name": "im2col-sweep-cap64k"}}) is None


def test_program_without_spans_reads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "RESULTS_DIR", str(tmp_path))
    path = _record(tmp_path / "trace" / "im2col-sweep-cap64k-3", False)
    ctx = {"trace": {"window": tracing.Trace(path).window()},
           "cell": {"name": "im2col-sweep-cap64k"}}
    assert spans.run_spans(ctx) == []
    assert harness.reader("host_tail_ms")(ctx) is None
