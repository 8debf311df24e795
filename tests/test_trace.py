"""The serving path's own spans and counters (`repro.utils.trace`).

A served window under `jax.profiler` holds, per batch, ``dse.execute``
with its four engine phases nested inside it, all carrying the batch's
id, besides the front end's ``dse.form``, ``dse.dispatch_wait`` and
``dse.publish`` and the collector's ``py.gc``; the server counts each
row's queue wait from its admission to its batch's engine call.
"""
import collections
import gc
import glob
import os
import time

import jax
import pytest

from repro.core import gan as G
from repro.core.dse_api import GANDSE
from repro.core.explorer import ExplorerConfig
from repro.dataset.generator import generate_tasks
from repro.design_models.dnnweaver import DnnWeaverModel
from repro.serve import DSEServer, ServeConfig, ServeFrontend
from repro.utils import trace

MODEL = DnnWeaverModel()
PHASES = ("dse.gfwd", "dse.select", "dse.sync", "dse.host_tail")


@pytest.fixture(scope="module")
def engine(tiny_gan_cfg, small_dataset):
    cfg = tiny_gan_cfg(MODEL)
    g = GANDSE(MODEL, cfg,
               ExplorerConfig(prob_threshold=0.1, max_candidates=128))
    g.attach(small_dataset(MODEL, n=256),
             G.init_generator(jax.random.PRNGKey(3), cfg, MODEL.space))
    return g


def _spans(trace_dir):
    """Host events named dse.* or py.* -> [(name, start, end, line id,
    metadata)]."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("dse.", "py.")):
                    out.append((e.name, e.start_ns, e.end_ns,
                                (plane.name, k), dict(e.stats)))
    return out


def test_served_window_holds_nested_batch_spans(engine, tmp_path):
    tasks = generate_tasks(MODEL, 6, seed=2)
    engine.explore_tasks(tasks, seed=7)        # compile outside the trace
    srv = DSEServer(ServeConfig(max_batch=4))
    srv.register(engine)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with ServeFrontend(srv) as fe:
            futs = [fe.submit(MODEL.name, tasks.net_idx[i], tasks.lat_obj[i],
                              tasks.pow_obj[i], seed=7 + i) for i in range(6)]
            assert all(f.result(timeout=60).ok for f in futs)
            assert fe.wait_all(timeout=60)
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    spans = _spans(str(tmp_path))
    names = collections.Counter(n for n, *_ in spans)
    for name in ("dse.form", "dse.dispatch_wait", "py.gc"):
        assert names[name] >= 1, names
    execs = [s for s in spans if s[0] == "dse.execute"]
    assert len(execs) == srv.stats["batches"] >= 2
    assert len({s[4]["batch"] for s in execs}) == len(execs)
    for _, t0, t1, line, ids in execs:
        mine = [s for s in spans if s[4].get("batch") == ids["batch"]]
        inner = sorted((s for s in mine if s[0] in PHASES),
                       key=lambda s: s[1])
        assert [s[0] for s in inner] == list(PHASES)
        assert all(t0 <= s[1] <= s[2] <= t1 and s[3] == line for s in inner)
        assert [s[0] for s in mine].count("dse.publish") == 1
    # the engine's counters, through the server's summary
    counts = srv.summary()["engine_stats"][MODEL.name]
    assert 0 <= counts["select_replay_tiles"] <= counts["select_tiles"]
    assert counts["select_tiles"] >= srv.stats["batches"]
    # DNNWeaver's groups (at most 8 choices) take the gather-free decode
    assert counts["select_gather_free_tiles"] == counts["select_tiles"]


def test_collector_spans_only_while_running(engine):
    fe = ServeFrontend(DSEServer(ServeConfig(max_batch=4)))
    hook = fe._gc_spans._on_gc
    assert hook not in gc.callbacks
    fe.server.register(engine)
    with fe:
        assert gc.callbacks.count(hook) == 1
    assert hook not in gc.callbacks


def test_spans_carry_the_bound_ids(monkeypatch):
    """`bind` adds its ids to every span the thread opens inside it; a span's
    own ids come on top."""
    calls = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name, **ids: calls.append((name, ids)))
    trace.span("a")
    with trace.bind(batch=3):
        trace.span("b")
        trace.span("c", generation=2)
    trace.span("d")
    assert calls == [("a", {}), ("b", {"batch": 3}),
                     ("c", {"batch": 3, "generation": 2}), ("d", {})]


def test_queue_wait_counts_the_time_before_dispatch(engine):
    tasks = generate_tasks(MODEL, 3, seed=5)
    srv = DSEServer(ServeConfig(max_batch=4))
    srv.register(engine)
    engine.explore_tasks(tasks, seed=100)      # compile outside the wait
    for i in range(3):
        srv.submit(MODEL.name, tasks.net_idx[i], tasks.lat_obj[i],
                   tasks.pow_obj[i], seed=100 + i)
    time.sleep(0.2)
    t = time.perf_counter()
    srv.drain()
    assert srv.stats["dispatched_rows"] == 3
    assert 3 * 0.2 <= srv.stats["queue_wait_s"] \
        <= 3 * (time.perf_counter() - t + 0.2) + 0.1
    # a cache hit never queues, and adds no wait
    before = srv.stats["queue_wait_s"]
    srv.submit(MODEL.name, tasks.net_idx[0], tasks.lat_obj[0],
               tasks.pow_obj[0], seed=100)
    srv.drain()
    assert srv.stats["queue_wait_s"] == before
