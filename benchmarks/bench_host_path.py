"""Host-path microbenchmark of the serving request path: admission,
micro-batch formation and publication, with no device involved.

A stub engine answers every task batch with one prebuilt `DSEResult`, so
what is timed is the serving layer's own Python: `DSEServer.submit`
(admission), `DSEServer.form_batch` (shedding + `MicroBatcher.next_batch`)
and `DSEServer.publish_batch` (cache, responses, callbacks).  Every request
carries its own seed, so the result cache never answers (as in the chip
benchmark's sweeps), and the server is never drained, so its outbox and
response map sit at ``response_retention`` as under a `ServeFrontend`.

``--frontend`` instead drives a `ServeFrontend` closed loop (``--clients``
callers, one sending thread, completion callbacks on the dispatcher) and
reads each thread's CPU time (`time.thread_time` for the sender, the
thread CPU clocks of the former and the dispatcher): with the GIL shared
by the three threads, their summed interpreter time per batch is what
bounds the rate once the device is fast.

  PYTHONPATH=src python benchmarks/bench_host_path.py [--requests N]
      [--rounds R] [--frontend [--clients C] [--seconds S] [--engine-ms M]]

Timings are of the host that runs it; they size host-path changes and
are no device metric.  No test depends on them.
"""
from __future__ import annotations

import argparse
import os
import queue
import statistics
import threading
import time
from typing import Dict, List

os.environ.setdefault("JAX_PLATFORMS", "cpu")   # never claims an accelerator

import numpy as np

from repro.core.dse_api import DSEResult
from repro.core.selector import Selection
from repro.design_models.im2col import Im2colModel
from repro.serve import DSEServer, FrontendConfig, ServeConfig, ServeFrontend

MAX_BATCH = 64


class StubEngine:
    """`DSEMethod` stand-in: one prebuilt result per task row, no device;
    ``engine_s`` of sleep (the GIL released, as while a device computes)
    per call."""

    method_name = "stub"

    def __init__(self, model, engine_s: float = 0.0):
        self.model = model
        self.engine_s = engine_s
        sel = Selection(cfg_idx=np.zeros(model.space.n_dims, np.int64),
                        latency=1.0, power=1.0, satisfied=True,
                        n_candidates=1)
        self._result = DSEResult(sel, 1.0, 1.0, 0.0)

    def explore_tasks(self, tasks, seed=0) -> List[DSEResult]:
        if self.engine_s > 0:
            time.sleep(self.engine_s)
        return [self._result] * len(tasks)


def _traffic(model, n: int, seed: int):
    rng = np.random.default_rng(seed)
    sizes = np.asarray(model.net_space.group_sizes)
    net = rng.integers(0, sizes, size=(n, sizes.size), dtype=np.int64)
    lat = rng.uniform(1e-4, 1e-2, n)
    pw = rng.uniform(0.5, 5.0, n)
    return net, lat, pw


def server_phases(n: int, rounds: int) -> Dict[str, float]:
    """Median over ``rounds`` of microseconds per request in each phase of
    the synchronous pump (the first round fills the outbox and is not
    counted)."""
    model = Im2colModel()
    srv = DSEServer(ServeConfig(max_batch=MAX_BATCH))
    eng = srv.register(StubEngine(model))
    net, lat, pw = _traffic(model, n, seed=0)
    per: Dict[str, List[float]] = {"admit": [], "form": [], "publish": []}
    seed = 0
    for r in range(rounds + 1):
        t0 = time.perf_counter()
        for i in range(n):
            srv.submit(model.name, net[i], lat[i], pw[i], seed=seed)
            seed += 1
        t_admit = time.perf_counter() - t0
        t_form = t_pub = 0.0
        while True:
            t0 = time.perf_counter()
            batch = srv.form_batch()
            t1 = time.perf_counter()
            if batch is None:
                break
            results = eng.explore_tasks(batch.tasks, seed=batch.seeds)
            info = {"degraded": False, "probe": None, "elapsed": 0.0,
                    "t_start": t1}
            t2 = time.perf_counter()
            srv.publish_batch(batch, results, info)
            t_pub += time.perf_counter() - t2
            t_form += t1 - t0
        if r:
            per["admit"].append(t_admit / n * 1e6)
            per["form"].append(t_form / n * 1e6)
            per["publish"].append(t_pub / n * 1e6)
    out = {k: statistics.median(v) for k, v in per.items()}
    out["total"] = sum(out.values())
    return out


def _cpu_s(thread: threading.Thread) -> float:
    return time.clock_gettime(time.pthread_getcpuclockid(thread.ident))


def frontend_closed_loop(clients: int, seconds: float,
                         engine_ms: float) -> Dict[str, float]:
    """Closed loop through a `ServeFrontend`: answers per second and each
    thread's CPU microseconds per answer over the window."""
    model = Im2colModel()
    srv = DSEServer(ServeConfig(max_batch=MAX_BATCH))
    srv.register(StubEngine(model, engine_ms * 1e-3))
    net, lat, pw = _traffic(model, 1 << 16, seed=1)
    ready: "queue.SimpleQueue[int]" = queue.SimpleQueue()
    done = [0]
    with ServeFrontend(srv, FrontendConfig()) as fe:
        former, dispatcher = fe._threads
        sent = [0]

        def send(client: int) -> None:
            i = sent[0]
            sent[0] += 1
            k = i % len(net)
            fut = fe.submit(model.name, net[k], lat[k], pw[k], seed=i)

            def answered(_f, client=client):
                done[0] += 1
                ready.put(client)
            fut.add_done_callback(answered)

        for cl in range(clients):
            send(cl)
        t0, c0 = time.perf_counter(), time.thread_time()
        f0, d0, n0 = _cpu_s(former), _cpu_s(dispatcher), done[0]
        b0 = srv.stats["batches"]
        t_end = t0 + seconds
        while time.perf_counter() < t_end:
            try:
                cl = ready.get(timeout=max(t_end - time.perf_counter(), 0))
            except queue.Empty:
                break
            send(cl)
        wall = time.perf_counter() - t0
        n = done[0] - n0
        cpu = {"sender": time.thread_time() - c0,
               "former": _cpu_s(former) - f0,
               "dispatcher": _cpu_s(dispatcher) - d0}
        batches = srv.stats["batches"] - b0
    out = {"answers_per_s": n / wall,
           "rows_per_batch": n / max(batches, 1)}
    for k, v in cpu.items():
        out[f"{k}_us_per_req"] = v / max(n, 1) * 1e6
    out["interp_us_per_req"] = sum(cpu.values()) / max(n, 1) * 1e6
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--requests", type=int, default=4096)
    ap.add_argument("--rounds", type=int, default=7)
    ap.add_argument("--frontend", action="store_true")
    ap.add_argument("--clients", type=int, default=256)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--engine-ms", type=float, default=4.0,
                    help="--frontend: stub engine time per batch")
    a = ap.parse_args()
    if a.frontend:
        res = frontend_closed_loop(a.clients, a.seconds, a.engine_ms)
    else:
        res = server_phases(a.requests, a.rounds)
        res = {f"{k}_us_per_req": v for k, v in res.items()}
    for k, v in res.items():
        print(f"{k:>26s} {v:10.3f}")


if __name__ == "__main__":
    main()
