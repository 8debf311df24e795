"""Train-while-serve driver: the online improvement loop on a live server.

  PYTHONPATH=src python -m repro.launch.online --model dnnweaver \
      --waves 6 --wave-size 16 [--generations 3] [--corrupt-step N]

Hosts one engine behind the production front end (`ServeFrontend`), wires
the `OnlineLoop` trainer onto it (harvest unsatisfied requests -> mine
hard examples -> incremental train -> checkpoint -> lock-disciplined hot
swap), and pushes waves of deliberately hard requests (tight objective
slack) while the trainer improves the generator between waves.  Each wave
uses fresh seeds, so nothing is answered from the cache and the reported
satisfied counts track the *current* generation's quality.

``--corrupt-step N`` flips payload bytes in generation N's checkpoint
right after it is written (`repro.serve.faults.corrupt_checkpoint`): the
swap's read-back detects the damage and serving falls back to the
previous good generation — the recovery path the soak harness
(`benchmarks/bench_online.py`) gates on.
"""
from __future__ import annotations

import argparse
import sys
import tempfile
import time

import jax
import numpy as np

from repro.core import gan as G
from repro.core.dse_api import GANDSE
from repro.core.explorer import ExplorerConfig
from repro.dataset.generator import generate_dataset, generate_tasks
from repro.design_models.dnnweaver import DnnWeaverModel
from repro.design_models.im2col import Im2colModel
from repro.design_models.tpu_mesh import DeepSeekV3Mesh, TpuMeshModel
from repro.serve import (DSEServer, FrontendConfig, OnlineConfig, OnlineLoop,
                         ServeConfig, ServeFrontend, corrupt_checkpoint)

MODELS = {m.name: m for m in (DnnWeaverModel, Im2colModel, TpuMeshModel,
                              DeepSeekV3Mesh)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="dnnweaver", choices=sorted(MODELS))
    ap.add_argument("--waves", type=int, default=6,
                    help="request waves pushed through the front end")
    ap.add_argument("--wave-size", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--neurons", type=int, default=64)
    ap.add_argument("--data", type=int, default=512)
    ap.add_argument("--slack", type=float, default=1.05,
                    help="objective slack upper bound; close to 1.0 makes "
                         "requests hard (Pareto-adjacent objectives)")
    ap.add_argument("--generations", type=int, default=0,
                    help="stop training after N generations (0 = no cap)")
    ap.add_argument("--min-hard", type=int, default=8,
                    help="buffered hard tasks that trigger a generation")
    ap.add_argument("--train-iters", type=int, default=4)
    ap.add_argument("--replay", type=int, default=64)
    ap.add_argument("--keep-last-n", type=int, default=3)
    ap.add_argument("--checkpoint-dir", default="",
                    help="checkpoint directory (default: a temp dir)")
    ap.add_argument("--corrupt-step", type=int, default=-1,
                    help="inject corruption into generation N's checkpoint "
                         "after saving (-1 = never): exercises the "
                         "fall-back-to-previous-generation swap path")
    ap.add_argument("--threshold", type=float, default=0.1)
    ap.add_argument("--max-candidates", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    model = MODELS[args.model]()
    gan_cfg = G.GANConfig(n_net=model.net_space.n_dims).scaled(
        layers=args.layers, neurons=args.neurons, batch_size=64)
    engine = GANDSE(model, gan_cfg,
                    ExplorerConfig(prob_threshold=args.threshold,
                                   max_candidates=args.max_candidates))
    ds = generate_dataset(model, args.data, seed=args.seed)
    init_key = jax.random.fold_in(jax.random.PRNGKey(args.seed), 3)
    engine.attach(ds, G.init_generator(init_key, gan_cfg, model.space))

    srv = DSEServer(ServeConfig(max_batch=args.max_batch))
    srv.register(engine)

    ckpt_dir = args.checkpoint_dir or tempfile.mkdtemp(prefix="dse_online_")

    def post_checkpoint(sdir: str) -> None:
        if args.corrupt_step >= 0 and \
                sdir.endswith(f"step_{args.corrupt_step:09d}"):
            corrupt_checkpoint(sdir, seed=args.seed)
            print(f"[online] injected corruption into {sdir}")

    ocfg = OnlineConfig(min_hard=args.min_hard,
                        train_iters=args.train_iters,
                        replay_capacity=args.replay,
                        keep_last_n=args.keep_last_n,
                        max_generations=args.generations,
                        seed=args.seed,
                        post_checkpoint=post_checkpoint)

    n = args.wave_size
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    sat_per_wave = []
    with ServeFrontend(srv, FrontendConfig()) as fe:
        with OnlineLoop(fe, model.name, ckpt_dir, cfg=ocfg) as loop:
            loop.warmup()            # compile the epoch fn up front
            for w in range(args.waves):
                tasks = generate_tasks(model, n, seed=args.seed + 10 + w,
                                       slack=(1.0, args.slack))
                base = int(rng.integers(1 << 20)) * 1000
                futs = [fe.submit(model.name, tasks.net_idx[i],
                                  tasks.lat_obj[i], tasks.pow_obj[i],
                                  seed=base + i) for i in range(n)]
                responses = [f.result(timeout=300) for f in futs]
                sat = sum(1 for r in responses
                          if r.ok and r.result.satisfied)
                sat_per_wave.append(sat)
                m = loop.metrics()
                print(f"[online] wave={w} satisfied={sat}/{n} "
                      f"generation={m['generation']} "
                      f"serving_step={m['serving_step']} "
                      f"buffered={m['buffer']['size']} "
                      f"swaps={m['swaps']} "
                      f"fallbacks={m['swap_fallbacks']}")
                # let the trainer catch up between waves so later waves
                # are served by later generations
                deadline = time.time() + 60
                while ((len(loop.buffer) >= ocfg.min_hard or loop.training)
                       and time.time() < deadline
                       and not (args.generations > 0
                                and loop.generation >= args.generations)):
                    time.sleep(0.05)
            final = loop.metrics()
    dt = time.time() - t0

    s = srv.summary()
    print(f"[online] model={model.name} waves={args.waves} "
          f"satisfied/wave={sat_per_wave} "
          f"generations={final['generations']} swaps={final['swaps']} "
          f"fallbacks={final['swap_fallbacks']} "
          f"errors={final['generation_errors']} "
          f"mined={final['mined_rows']} "
          f"stale_cache_skips={s['stale_cache_skips']} "
          f"invalidations={s['cache']['invalidations']} "
          f"params_gen={s['params_generation']} "
          f"checkpoints={final['checkpoint_steps']} "
          f"wall={dt:.1f}s ckpt_dir={ckpt_dir}")
    assert final["generation_errors"] == 0, final
    assert s["pending"] == 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
