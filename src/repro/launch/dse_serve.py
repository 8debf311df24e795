"""DSE serving driver: micro-batching loop over a request queue.

  PYTHONPATH=src python -m repro.launch.dse_serve --model im2col \
      --requests 64 --max-batch 16 [--concurrent]

The DSE twin of `repro.launch.serve` (the LM continuous-batching driver):
requests are admitted into a `DSEServer`, coalesced into pow2-bucketed
micro-batches, dispatched through the engine's batched exploration path,
and answered with per-request `DSEResult`s.  A random-init generator is
attached by default (serving throughput does not depend on training
quality); pass --train-iters to train first and report real satisfied
counts.

``--concurrent`` serves the same workload through the production front
end (`repro.serve.frontend.ServeFrontend`): non-blocking submits with
futures, continuous batching overlapping host-side batch formation with
in-flight device compute, and admission control — pair with --max-queue
(bounded queues, shed-at-the-door) and --deadline-s (per-request
deadlines) to see load shedding in the report.

The exit code is nonzero when any request FAILED, was never answered, or
was answered by the server's degraded host route: that route returns the
same Selections, so only the exit code tells a broken device route from a
healthy one.
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import List, Optional, Tuple

import jax
import numpy as np

from repro.core import gan as G
from repro.core.dse_api import GANDSE, summarize
from repro.core.explorer import ExplorerConfig
from repro.core.selector import set_select_route
from repro.dataset.generator import DSETask, generate_dataset, generate_tasks
from repro.design_models.dnnweaver import DnnWeaverModel
from repro.design_models.im2col import Im2colModel
from repro.design_models.tpu_mesh import DeepSeekV3Mesh, TpuMeshModel
from repro.launch.compile_cache import use_compile_cache
from repro.serve import DSEServer, ServeConfig
from repro.serve.request import SOURCE_FAILED, DSEResponse

MODELS = {m.name: m for m in (DnnWeaverModel, Im2colModel, TpuMeshModel,
                              DeepSeekV3Mesh)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="im2col", choices=sorted(MODELS))
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--neurons", type=int, default=64)
    ap.add_argument("--data", type=int, default=512)
    ap.add_argument("--train-iters", type=int, default=0,
                    help="0 = attach a random-init G (throughput only)")
    ap.add_argument("--threshold", type=float, default=0.1)
    ap.add_argument("--max-candidates", type=int, default=2048)
    ap.add_argument("--cache", type=int, default=4096,
                    help="LRU result-cache capacity; 0 disables")
    ap.add_argument("--repeat-frac", type=float, default=0.25,
                    help="fraction of requests re-submitted verbatim "
                         "(exercises the cache/coalescing path)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fused", choices=("auto", "on", "off"), default="auto",
                    help="Pallas fused-MLP dispatch: auto = backend rule "
                         "(TPU on, CPU/GPU off), on/off force it")
    ap.add_argument("--batch-route", choices=("fused", "dense"),
                    default="fused",
                    help="batched selection: fused streaming tiles "
                         "(default) or the dense reference route")
    ap.add_argument("--select-route", choices=("auto", "host", "device"),
                    default="auto",
                    help="per-task select() fallback route: auto = the "
                         "selector.JAX_MIN_CANDIDATES crossover, host/"
                         "device force one (see set_select_route)")
    ap.add_argument("--concurrent", action="store_true",
                    help="serve through the threaded production front end "
                         "(futures + continuous batching) instead of the "
                         "sync submit/drain pump")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="per-model admission bound; submissions past it "
                         "are REJECTED with a retry-after hint (0 = "
                         "unbounded)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request deadline for --concurrent; expired "
                         "requests are shed before dispatch (0 = none)")
    args = ap.parse_args(argv)
    use_compile_cache()
    use_fused = {"auto": None, "on": True, "off": False}[args.fused]
    set_select_route(args.select_route)

    model = MODELS[args.model]()
    gan_cfg = G.GANConfig(n_net=model.net_space.n_dims).scaled(
        layers=args.layers, neurons=args.neurons, batch_size=64)
    engine = GANDSE(model, gan_cfg,
                    ExplorerConfig(prob_threshold=args.threshold,
                                   max_candidates=args.max_candidates,
                                   batch_route=args.batch_route))
    if args.train_iters > 0:
        engine.train(args.data, args.train_iters, seed=args.seed)
    else:
        ds = generate_dataset(model, args.data, seed=args.seed)
        init_key = jax.random.fold_in(jax.random.PRNGKey(args.seed), 3)
        engine.attach(ds, G.init_generator(init_key, gan_cfg, model.space))

    srv = DSEServer(ServeConfig(max_batch=args.max_batch,
                                cache_capacity=args.cache,
                                max_queue=args.max_queue,
                                use_fused=use_fused))
    srv.register(engine)

    n = args.requests
    tasks = generate_tasks(model, n, seed=args.seed + 2)
    n_rep = int(n * args.repeat_frac)
    warm_bucket(srv, model.name, tasks, seed=args.seed)
    t0 = time.time()
    responses, fe_line = serve_tasks(
        srv, model.name, tasks, seed=args.seed, n_rep=n_rep,
        concurrent=args.concurrent,
        timeout_s=args.deadline_s if args.deadline_s > 0 else None)
    dt = time.time() - t0

    n_total = n + 2 * n_rep
    s = srv.summary()
    served = [r.result for r in responses if r.ok]
    stats = summarize(served)
    print(f"[dse_serve] model={model.name} "
          f"mode={'concurrent' if args.concurrent else 'sync'} "
          f"kernels={s['kernels']['backend']}:"
          f"{'fused' if s['kernels']['fused'][model.name] else 'jnp'} "
          f"requests={len(responses)}/{n_total} served={len(served)} "
          f"batches={s['batches']} mean_batch={s['mean_batch_size']:.1f} "
          f"coalesced={s['coalesced']} cache_hits={s['cache']['hits']} "
          f"satisfied={stats['n_satisfied']} {fe_line}"
          f"req/s={len(responses)/max(dt, 1e-9):.0f}")
    problems = serve_problems(srv, responses, n_total)
    for p in problems:
        print(f"[dse_serve] FAIL: {p}", file=sys.stderr)
    return 1 if problems else 0


def warm_bucket(srv: DSEServer, model_name: str, tasks: DSETask,
                seed: int = 0) -> None:
    """Compile the pow2(max_batch) bucket the served dispatches will use:
    one full micro-batch of off-range seeds, then a cache clear, so no
    served request is answered from warmup work."""
    n = len(tasks)
    for i in range(min(srv.cfg.max_batch, n)):
        srv.submit(model_name, tasks.net_idx[i], tasks.lat_obj[i],
                   tasks.pow_obj[i], seed=seed - 1_000_000 - i)
    srv.drain()
    srv.cache.clear()


def serve_tasks(srv: DSEServer, model_name: str, tasks: DSETask, *,
                seed: int = 0, n_rep: int = 0, concurrent: bool = False,
                timeout_s: Optional[float] = None
                ) -> Tuple[List[DSEResponse], str]:
    """Serve ``tasks`` (row i with seed + i) through ``srv``, plus ``n_rep``
    verbatim duplicates submitted while the originals are queued and
    ``n_rep`` repeats after they were answered; returns (responses,
    front-end report line).  ``concurrent`` serves through the threaded
    `ServeFrontend` instead of the sync submit/drain pump."""
    n = len(tasks)
    if not concurrent:
        def submit(rows):
            for i in rows:
                srv.submit(model_name, tasks.net_idx[i], tasks.lat_obj[i],
                           tasks.pow_obj[i], seed=seed + i)
        submit(range(n))
        # duplicates of still-queued requests coalesce (dispatch once)...
        submit(range(n_rep))
        responses = srv.drain()
        # ...and verbatim repeats of served requests hit the LRU cache
        submit(range(n_rep))
        return responses + srv.drain(), ""

    from repro.serve import FrontendConfig, ServeFrontend

    def push(fe, rows):
        return [fe.submit(model_name, tasks.net_idx[i], tasks.lat_obj[i],
                          tasks.pow_obj[i], seed=seed + i,
                          timeout_s=timeout_s) for i in rows]

    with ServeFrontend(srv, FrontendConfig()) as fe:
        # duplicates submitted while the originals are in flight
        # coalesce (or hit the cache, depending on dispatch timing)...
        futs = push(fe, range(n)) + push(fe, range(n_rep))
        responses = [f.result(timeout=300) for f in futs]
        # ...and verbatim repeats of served requests hit the LRU cache
        responses += [f.result(timeout=300) for f in push(fe, range(n_rep))]
        m = fe.metrics()["frontend"]["latency"]
    return responses, (f"p50={m['p50_ms']:.1f}ms p99={m['p99_ms']:.1f}ms "
                       f"rejected={srv.stats['rejected']} "
                       f"degraded={srv.stats['degraded_entered']} ")


def serve_problems(srv: DSEServer, responses: List[DSEResponse],
                   n_total: int) -> List[str]:
    """Why a serving run must not count as a success: a request that never
    terminated or is still queued, a FAILED response, or any use of the
    degraded host route (it answers correctly, so only these counters show
    that the device route broke).  Admission-control rejections are load
    shedding, not failures.  Empty when the run is healthy."""
    problems = []
    if len(responses) != n_total:
        problems.append(f"{len(responses)} of {n_total} requests terminated")
    if srv.batcher.pending():
        problems.append(f"{srv.batcher.pending()} requests still queued")
    failed = [r for r in responses if r.source == SOURCE_FAILED]
    if failed:
        problems.append(f"{len(failed)} requests FAILED, first: "
                        f"{failed[0].error}")
    degraded = sum(r.degraded for r in responses)
    if degraded or srv.stats["degraded_entered"]:
        problems.append(
            f"degraded host route entered {srv.stats['degraded_entered']} "
            f"times, {degraded} responses served by it")
    return problems


if __name__ == "__main__":
    sys.exit(main())
