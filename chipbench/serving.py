"""The serving cells: a `DSEServer` behind the threaded `ServeFrontend`,
one GANDSE engine on a G with random weights, driven by a closed or an
open loop, and the comparison with the plain reference.

Traffic comes from the mix file alone: loop kind, clients or rate, batch
cap, the engine's threshold and candidate cap, the task slack, and how
many answers the check compares.

Every run serves the same work in its own order: G's weights, the task
pool and each task's request seed come from the mix's ``work_seed``, as
one deployed model serves all traffic; ``--seed`` orders the pool (and
the open loop's gaps).  So runs on different seeds do the same kind of
work, and the same seed gives the same inputs.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import heapq
import importlib
import queue
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import harness, reference, tracing
from chipbench.compiles import GcPauses


def rng_seed(seed: int, stream: int):
    """Independent streams from one run seed of any size."""
    return np.random.SeedSequence([int(seed) % (1 << 63), stream])


@dataclasses.dataclass
class Answer:
    """One sampled answer: the request's index in the run, its seed and the
    Selection it came back with."""
    row: int
    seed: int
    sel: object = None


class Book:
    """The run's requests as numbers: when each was due (open loop) or sent
    (closed loop), when it was sent and answered, and whether it came back
    DONE.  Only the sampled answers keep their Selection:
    the ``keep`` of lowest priority (drawn from the seed per request) among
    those answered DONE, and the one of most candidates.  So the window
    holds no object per request beyond what the program itself keeps, and
    the sample depends on which requests were answered, not on the order."""

    def __init__(self, n_max: int, keep: int, seed: int):
        self.t_due, self.t_sub, self.t_done = (np.zeros(n_max)
                                               for _ in range(3))
        self.state = np.full(n_max, -1, np.int8)   # -1 waiting, 0 not DONE, 1
        self.req = np.zeros(n_max, np.int64)       # request index in the run
        self.seeds = np.zeros(n_max, np.int64)
        self.n = 0
        self._rng = np.random.default_rng(rng_seed(seed, 5))
        self.prio = self._rng.random(n_max)
        self.keep = keep
        self._kept: list = []     # heap of (-priority, j, sel)
        self._most = None         # (n_candidates, -j, sel)
        self._lock = threading.Lock()

    def add(self, i: int, seed: int, t_due: float) -> int:
        """Book the run's request ``i`` (from the sending thread only)."""
        j = self.n
        if j == len(self.state):
            with self._lock:          # answers write under the same lock
                for name in ("t_due", "t_sub", "t_done", "req", "seeds"):
                    a = getattr(self, name)
                    setattr(self, name, np.concatenate([a, np.zeros_like(a)]))
                self.state = np.concatenate(
                    [self.state, np.full(j, -1, np.int8)])
                self.prio = np.concatenate([self.prio, self._rng.random(j)])
        self.req[j], self.seeds[j], self.t_due[j] = i, seed, t_due
        self.n = j + 1
        return j

    def answer(self, j: int, f) -> None:
        """Done-callback of the request at position ``j``."""
        t = time.perf_counter()
        resp = f.result()
        sel = resp.result.selection if resp.ok else None
        with self._lock:
            self.t_done[j] = t
            self.state[j] = 1 if resp.ok else 0
            if sel is None:
                return
            item = (-self.prio[j], j, sel)
            if len(self._kept) < self.keep:
                heapq.heappush(self._kept, item)
            elif item[0] > self._kept[0][0]:
                heapq.heapreplace(self._kept, item)
            key = (sel.n_candidates, -j)
            if self._most is None or key > self._most[:2]:
                self._most = (*key, sel)

    def waiting(self) -> bool:
        return bool((self.state[:self.n] < 0).any())

    def sample(self) -> List[Answer]:
        """The sampled answers (see the class), the one of most candidates
        in place of the kept one of highest priority."""
        kept = {j: sel for _, j, sel in self._kept}
        if self._most is not None:
            j = -self._most[1]
            if j not in kept and len(kept) >= self.keep:
                del kept[max(kept, key=lambda i: self.prio[i])]
            kept[j] = self._most[2]
        return [Answer(int(self.req[j]), int(self.seeds[j]), kept[j])
                for j in sorted(kept)]


class ServingCell:
    """Set-up of one serving cell: data, weights, engine, server."""

    def __init__(self, c: dict, seed: int):
        """``seed`` orders the work; the work itself is the mix's."""
        from repro.core import gan as G
        from repro.core.dse_api import GANDSE
        from repro.core.encoding import Normalizer, binary_log2_encode
        from repro.core.explorer import ExplorerConfig
        from repro.dataset.generator import Dataset, DSETask
        from repro.serve import DSEServer, ServeConfig

        import jax

        self.c, self.cfg, self.mix = c, c["cfg"], c["mix"]
        cfg, mix = self.cfg, self.mix
        mod, cls = cfg["program_model"].split(":")
        self.model = getattr(importlib.import_module(mod), cls)()
        self.oracle = reference.Oracle(cfg)
        check_spaces(self.model, cfg)
        self.DSETask = DSETask

        n_net = self.oracle.net_space.n_dims
        self.gcfg = G.GANConfig(
            n_net=n_net, n_obj=cfg["n_obj"], noise_dim=cfg["noise_dim"],
            g_hidden_layers=cfg["g_hidden_layers"], g_neurons=cfg["g_neurons"],
            d_hidden_layers=cfg["d_hidden_layers"], d_neurons=cfg["d_neurons"],
            g_lr=cfg["g_lr"], d_lr=cfg["d_lr"], w_critic=cfg["w_critic"],
            batch_size=cfg["batch_size"], dtype=cfg["dtype"])
        self.g_shapes = reference.mlp_shapes(
            n_net + cfg["n_obj"] + cfg["noise_dim"], cfg["g_neurons"],
            cfg["g_hidden_layers"], self.oracle.space.onehot_width)

        # the encoders' data set (normalisers only) and the task pool
        work = mix["work_seed"]
        net, cf, lat, pw = reference.sample_rows(
            self.oracle, mix["dataset_rows"], seed=rng_seed(work, 1))
        fit = lambda x: Normalizer.fit(binary_log2_encode(x), center=True)
        self.ds = Dataset(self.model.name, net, cf, lat, pw,
                          lat_norm=fit(lat[:, None]), pow_norm=fit(pw[:, None]),
                          net_norm=fit(self.model.net_space
                                       .values_from_indices(net)))
        self.enc = reference.Encoder(self.oracle.net_space, net, lat, pw)
        self.tasks = reference.sample_tasks(
            self.oracle, mix["task_pool"], seed=rng_seed(work, 2),
            slack=tuple(mix["slack"]))
        # each pool task's request seed, distinct; the run's order of them
        self.seed_base = int(np.random.default_rng(rng_seed(work, 3))
                             .integers(0, 1 << 40))
        self.order = np.random.default_rng(rng_seed(seed, 6)).permutation(
            mix["task_pool"])

        key = jax.random.PRNGKey(int(work) & 0xFFFFFFFF)
        self.params = jax.block_until_ready(
            reference.make_init(self.g_shapes)(key))
        self.engine = GANDSE(self.model, self.gcfg, ExplorerConfig(
            prob_threshold=mix["prob_threshold"],
            max_candidates=mix["max_candidates"]))
        self.engine.attach(self.ds, self.params)
        self.srv = DSEServer(ServeConfig(max_batch=mix["max_batch"]))
        self.srv.register(self.engine)

    def pool_row(self, i: int) -> int:
        """The pool task the run sends as its i-th request.  A window sends
        fewer requests than the pool holds; past its end the tasks come
        round again with new request seeds (req_seed), so none repeats."""
        return int(self.order[i % len(self.order)])

    def task(self, i: int):
        net, lo, po = self.tasks
        r = self.pool_row(i)
        return net[r], float(lo[r]), float(po[r])

    def req_seed(self, i: int) -> int:
        lap = i // len(self.order)
        return self.seed_base + self.pool_row(i) + lap * len(self.order)

    def warm(self) -> None:
        """Compile every batch bucket the window can form (powers of two up
        to max_batch) through the engine entry the server calls.  The
        candidate cap is an argument of the compiled select, not part of
        its shape, so the warm-up runs at cap 1: the same programs, one
        tile each."""
        net, lo, po = self.tasks
        xcfg = self.engine.explorer_cfg
        cap, xcfg.max_candidates = xcfg.max_candidates, 1
        try:
            k = 1
            while k <= self.mix["max_batch"]:
                rows = np.arange(k)
                self.engine.explore_tasks(
                    self.DSETask(net[rows], lo[rows], po[rows]),
                    seed=-1_000_000 - rows)
                k *= 2
        finally:
            xcfg.max_candidates = cap

    def stats(self) -> dict:
        s = self.srv.stats
        return {k: s[k] for k in ("batches", "dispatched_rows",
                                  "padded_rows", "dispatch_s", "retried",
                                  "degraded_batches")}

    def book(self, seed: int) -> Book:
        return Book(self.mix["task_pool"], self.mix["check_sample"], seed)

    def span_batches(self) -> List[dict]:
        """Traced runs only: wrap the engine call of each batch in a host
        span ``bench.batch.<k>`` and keep, per batch k, the rows it ran
        (padding included) and the candidates they scanned, so that a
        program's device time and the work it did are read over the same
        batches."""
        import jax
        real, log = self.srv.execute_batch, []

        def traced(batch):
            with jax.profiler.TraceAnnotation(f"bench.batch.{len(log)}"):
                results, info = real(batch)
            log.append({"rows": len(results), "candidates": sum(
                r.selection.n_candidates for r in results
                if r.selection is not None)})
            return results, info
        self.srv.execute_batch = traced
        return log


def check_spaces(model, cfg: dict) -> None:
    """The program must run the spaces the configuration file states."""
    for attr, key in (("space", "config_space"), ("net_space", "net_space")):
        got = {d.name: [float(x) for x in d.choices]
               for d in getattr(model, attr).dims}
        want = {k: [float(x) for x in v] for k, v in cfg[key].items()}
        if list(got.items()) != list(want.items()):
            raise harness.BenchError(
                f"{model.name} {attr} differs from {cfg['name']}'s {key}")


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
def _span(name: str, on: bool):
    if on:
        import jax
        return jax.profiler.TraceAnnotation(name)
    import contextlib
    return contextlib.nullcontext()


def closed_loop(sc: ServingCell, fe, book: Book, seconds: float,
                spans: bool):
    """``clients`` callers, each sending its next request when its last is
    answered.  Latency runs from the send."""
    ready: "queue.SimpleQueue[int]" = queue.SimpleQueue()

    def send(client: int) -> None:
        i = book.n
        net, lo, po = sc.task(i)
        j = book.add(i, sc.req_seed(i), time.perf_counter())
        book.t_sub[j] = book.t_due[j]
        with _span("bench.submit", spans):
            fut = fe.submit(sc.model.name, net, lo, po, seed=sc.req_seed(i))

        def done(f, j=j, client=client):
            book.answer(j, f)
            ready.put(client)
        fut.add_done_callback(done)

    t_start = time.perf_counter()
    t_end = t_start + seconds
    for cl in range(sc.mix["clients"]):
        send(cl)
    while True:
        left = t_end - time.perf_counter()
        if left <= 0:
            break
        with _span("bench.wait", spans):
            try:
                cl = ready.get(timeout=left)
            except queue.Empty:
                break
        if time.perf_counter() < t_end:
            send(cl)
    return t_start, t_end, {}


def open_loop(sc: ServingCell, fe, book: Book, seconds: float, spans: bool,
              rate: Optional[float] = None, seed: int = 0, first: int = 0):
    """Poisson arrivals at the mix's fixed rate.  Every seed gets the same
    set of gaps (drawn once from the mix's own seed, scaled to fill the
    window) in its own order; latency runs from the due time.  Requests
    are the run's ``first``-th onwards."""
    rate = rate or sc.mix["rate_rps"]
    n = max(int(round(rate * seconds)), 1)
    gaps = np.random.default_rng(sc.mix["gap_seed"]).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(rng_seed(seed, 4)).permutation(gaps)
    t_start = time.perf_counter()
    due = t_start + np.cumsum(gaps) - gaps[0]
    for k in range(n):
        wait = due[k] - time.perf_counter()
        if wait > 0:
            with _span("bench.wait", spans):
                time.sleep(wait)
        i = first + k
        net, lo, po = sc.task(i)
        j = book.add(i, sc.req_seed(i), float(due[k]))
        book.t_sub[j] = time.perf_counter()
        with _span("bench.submit", spans):
            fut = fe.submit(sc.model.name, net, lo, po, seed=sc.req_seed(i))
        fut.add_done_callback(functools.partial(book.answer, j))
    t_end = t_start + seconds
    lag = book.t_sub[book.n - n:book.n] - book.t_due[book.n - n:book.n]
    return t_start, t_end, {
        "generator_lag_p95_ms": float(np.percentile(lag, 95) * 1e3),
        "generator_lag_max_ms": float(lag.max() * 1e3)}


def finish(fe, book: Book, timeout: float = 120.0) -> None:
    """Wait for every request sent to be answered."""
    end = time.perf_counter() + timeout
    while book.waiting() and time.perf_counter() < end:
        time.sleep(0.01)
    fe.wait_all(timeout=max(end - time.perf_counter(), 0.1))


# ---------------------------------------------------------------------------
# the comparison with the plain reference
# ---------------------------------------------------------------------------
def program_probs(sc: ServingCell, net, lo, po, seeds) -> np.ndarray:
    """G's probabilities from the window's own compiled forward, in
    batches of the window's size (max_batch, the last one padded)."""
    b = sc.mix["max_batch"]
    out = []
    for s in range(0, len(lo), b):
        rows = np.arange(s, min(s + b, len(lo)))
        pad = np.concatenate([rows, np.full(b - len(rows), rows[-1])])
        p = sc.engine._explorer.generator_probs(
            net[pad], lo[pad], po[pad], seed=np.asarray(seeds)[pad])
        out.append(p[:len(rows)])
    return np.concatenate(out)


def compare(sc: ServingCell, sample: List[Answer], control: bool = False
            ) -> Dict[str, float]:
    """The numbers that decide ``correct`` for the sampled answers.

    probs_gap   widest gap of a probability of G against the reference
                forward at "highest";
    select_miss answers whose chosen configuration or candidate count
                differs from the reference enumeration and sequential
                Algorithm 2 run on the same probabilities;
    metric_gap  widest relative gap of an answer's latency or power against
                the float64 oracle on its configuration (1 where the
                satisfied flag disagrees).

    ``control`` puts the reference, one precision lower, in the program's
    place (see reference.py) and reads the same numbers."""
    net = np.stack([sc.task(r.row)[0] for r in sample])
    lo = np.array([sc.task(r.row)[1] for r in sample])
    po = np.array([sc.task(r.row)[2] for r in sample])
    seeds = np.array([r.seed for r in sample], np.int64)
    ne, oe = sc.enc.net_enc(net), sc.enc.obj_enc(lo, po)
    space, thr, cap = sc.oracle.space, sc.mix["prob_threshold"], \
        sc.mix["max_candidates"]
    ref_probs = reference.make_g_probs(space, sc.cfg["noise_dim"])(
        sc.params, ne, oe, seeds)
    if control:
        probs = reference.make_g_probs(space, sc.cfg["noise_dim"], True)(
            sc.params, ne, oe, seeds)
        answers = [reference.select(sc.oracle, net[t], probs[t], thr, cap,
                                    lo[t], po[t], control=True)
                   for t in range(len(sample))]
    else:
        probs = program_probs(sc, net, lo, po, seeds)
        answers = []
        for r in sample:
            s = r.sel
            answers.append((s.cfg_idx, s.latency, s.power, s.satisfied,
                            s.n_candidates))
    miss, mgap = 0, 0.0
    for t, (c_idx, lat, pw, sat, n_c) in enumerate(answers):
        ref = reference.select(sc.oracle, net[t], probs[t], thr, cap,
                               lo[t], po[t])
        same = (n_c == ref[4] and (c_idx is None) == (ref[0] is None)
                and (c_idx is None or np.array_equal(c_idx, ref[0])))
        miss += 0 if same else 1
        if c_idx is not None:
            l64, p64 = sc.oracle(net[t][None], np.asarray(c_idx)[None])
            l64, p64 = float(l64[0]), float(p64[0])
            gap = max(abs(lat - l64) / abs(l64), abs(pw - p64) / abs(p64))
            if sat != reference.satisfied(l64, p64, lo[t], po[t]):
                gap = 1.0
            mgap = max(mgap, gap)
    return {"probs_gap": float(np.max(np.abs(probs - ref_probs))),
            "select_miss": float(miss), "metric_gap": float(mgap)}


# ---------------------------------------------------------------------------
# one run of a serving cell
# ---------------------------------------------------------------------------
def settle() -> None:
    """End of set-up: collect what set-up left behind, so every window
    starts from the same heap."""
    gc.collect()


class Profile:
    """A traced run's profile: ``seconds`` of the load's steady state,
    from ``LEAD_S`` into the window (a closed loop's first batches form
    while its clients are still sending), with the server's counters read
    at the traced span's edges.  The span ``bench.window`` runs on a
    thread of its own, so the loop itself is the untraced run's."""

    LEAD_S, TAIL_S = 5.0, 1.0

    def __init__(self, sc: ServingCell, tdir: str, seconds: float,
                 budget: float):
        self.sc, self.dir = sc, tdir
        self.seconds = min(seconds, budget)
        self.lead = max(min(self.LEAD_S, budget - self.seconds - self.TAIL_S),
                        0.0)
        self.loop_seconds = min(budget, self.lead + self.seconds
                                + self.TAIL_S)
        self.stats: dict = {}
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        import jax
        time.sleep(self.lead)
        jax.profiler.start_trace(self.dir)
        s0 = self.sc.stats()
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            time.sleep(self.seconds)
        s1 = self.sc.stats()
        self.stats = {k: s1[k] - s0[k] for k in s0}

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        import jax
        self._thread.join()
        jax.profiler.stop_trace()


def _pct(x, q):
    return float(np.percentile(np.asarray(x, np.float64), q)) if len(x) \
        else float("nan")


def run(c: dict, args, clock: harness.Clock, loop, counter) -> tuple:
    """Set up, warm, measure one window, check.  -> (result, checks)."""
    from repro.serve import ServeFrontend

    devs = harness.devices(c["chips"])
    peak = harness.peaks(devs[0].device_kind)
    sc = ServingCell(c, args.seed)
    sc.warm()
    trace = bool(args.trace)
    batches = sc.span_batches() if trace else []
    fe = ServeFrontend(sc.srv).start()
    book = sc.book(args.seed)
    settle()
    seconds, profile = args.seconds, None
    if trace:
        profile = Profile(sc, harness.trace_dir(c["name"], args.seed),
                          sc.mix["trace_seconds"], args.seconds)
        seconds = profile.loop_seconds
    s0, c0 = sc.stats(), counter.count
    setup_s = clock.setup_s()
    pauses = GcPauses()
    if profile:
        profile.start()
    t0, t1, gen = (closed_loop(sc, fe, book, seconds, trace)
                   if loop == "closed" else
                   open_loop(sc, fe, book, seconds, trace, seed=args.seed))
    while time.perf_counter() < t1:
        time.sleep(min(t1 - time.perf_counter(), 0.01))
    s1 = sc.stats()
    gen.update(pauses.remove())
    gen.update({k: s1[k] - s0[k] for k in ("retried", "degraded_batches")})
    if profile:
        profile.stop()
    finish(fe, book)
    fe.stop()
    compiles = counter.count - c0
    mem = harness.memory_peak(devs)

    n = book.n
    state, t_done = book.state[:n], book.t_done[:n]
    timed = state >= 0
    if loop == "closed":
        timed &= t_done <= t1
    lat_ms = (t_done - book.t_due[:n])[timed] * 1e3
    n_ok = int((state[timed] == 1).sum())
    failed = int((state != 1).sum())
    for k, v in gen.items():
        print(f"[chipbench] {k} {v}", file=sys.stderr)

    e2e = {"setup_s": setup_s, "serve_rps": n_ok / seconds,
           "serve_p95_ms": _pct(lat_ms, 95)}
    result = {"attempted": n, "failed": failed,
              "device": harness.device_info(devs, mem)}
    if trace:
        path = tracing.find_xplane(profile.dir)
        tr = tracing.reduce(path) if path else None
        ctx = {"trace": tr, "stats": profile.stats, "peak": peak, "cell": c,
               "g_shapes": sc.g_shapes, "window_s": profile.seconds,
               "batches": batches}
        result["metrics"] = harness.read_per_layer(c, ctx)
        if tr is not None:
            result["device"]["busy_s"] = tr["busy_s"]
            result["device"]["window_s"] = tr["window_s"]
            result["breakdown"] = {
                "device_ops": [[n, s / tr["devices"]] for n, s in
                               tracing.top(tr["ops"])],
                "idle_gaps": [list(g) for g in tr["idle_gaps"]]}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in c["end_to_end"]}

    sample = book.sample()
    nums = compare(sc, sample) if sample else {}
    limits = sc.mix["limits"]
    checks = {k: [nums.get(k), limits[k]] for k in limits}
    checks["window_compiles"] = [compiles, 0]
    checks["failed"] = [failed, 0]
    result["correct"] = bool(sample) and all(
        v is not None and v <= l for v, l in checks.values())
    return result, checks


def sweep(c: dict, args, rates, counter) -> List[dict]:
    """The knee sweep: one set-up, then one open-loop window per offered
    rate.  Per rate: achieved rate (answers due in the window and done by
    its close, over its length), the backlog left at the close, the 95th
    percentile from the due time, and the generator's lag.  No request
    repeats across the steps, so none is answered from the cache."""
    from repro.serve import ServeFrontend
    harness.devices(c["chips"])
    sc = ServingCell(c, args.seed)
    sc.warm()
    settle()
    rows, first = [], 0
    for k, rate in enumerate(rates):
        fe = ServeFrontend(sc.srv).start()
        c0 = counter.count
        book = sc.book(args.seed + k)
        t0, t1, gen = open_loop(sc, fe, book, args.seconds, False, rate=rate,
                                seed=args.seed + k, first=first)
        first += book.n
        while time.perf_counter() < t1:
            time.sleep(0.01)
        backlog = int((book.state[:book.n] < 0).sum())
        finish(fe, book)
        fe.stop()
        t_done = book.t_done[:book.n]
        lat = (t_done - book.t_due[:book.n]) * 1e3
        rows.append({"offered_rps": rate,
                     "achieved_rps": int((t_done <= t1).sum()) / args.seconds,
                     "backlog_at_close": backlog,
                     "p50_ms": _pct(lat, 50), "p95_ms": _pct(lat, 95),
                     "compiles": counter.count - c0, **gen})
    return rows


def readings(c: dict, seeds, control_seeds, seconds: float, counter):
    """Limit readings: per seed, a short window at the cell's own load and
    the compared numbers of the program; on ``control_seeds`` also those
    of the control (the reference one precision lower in its place)."""
    from repro.serve import ServeFrontend
    harness.devices(c["chips"])
    loop = c["mix"]["driver"]
    for seed in seeds:
        sc = ServingCell(c, seed)
        sc.warm()
        fe = ServeFrontend(sc.srv).start()
        book = sc.book(seed)
        if loop == "closed":
            closed_loop(sc, fe, book, seconds, False)
        else:
            open_loop(sc, fe, book, seconds, False, seed=seed)
        finish(fe, book)
        fe.stop()
        sample = book.sample()
        row = {"seed": seed, "answers": book.n,
               "program": compare(sc, sample)}
        if seed in control_seeds:
            row["control"] = compare(sc, sample, control=True)
        yield row
