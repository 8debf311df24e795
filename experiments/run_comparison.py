"""Table-5 comparison harness: every DSE method, one shared experiment.

Reproduces the paper's headline experiment (Table 5, Fig. 5): train GANDSE
and the learned baselines on ONE shared dataset per design model, run the
same DSE task set through every method via the ``DSEMethod`` protocol, and
report satisfied counts, improvement ratio, DSE time, and candidate counts
side by side.

Fairness rules:

- every method explores the same tasks with the same seed;
- RandomSearch (the sanity floor, not in the paper's table) is budget
  -matched to GANDSE: its sample count is set to GANDSE's mean candidate
  count, so "GANDSE beats random search" is an equal-evaluation-budget
  claim;
- all methods serve the batch through their device-resident
  ``explore_tasks`` route (sequential host fallback for models without a
  jnp oracle), so DSE times compare the same serving discipline.

  PYTHONPATH=src python experiments/run_comparison.py [--quick]
      [--models dnnweaver im2col tpu_mesh]

Writes ``results/comparison_<model>.json`` per design model plus the
combined ``results/comparison.json``.  Reduced-scale defaults for CPU; the
paper scale (11-14 layers x 2048 neurons) is documented in EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.baselines.drl import PolicyGradientDRL
from repro.baselines.mlp import LargeMLP
from repro.baselines.random_search import RandomSearch
from repro.baselines.sa import SimulatedAnnealing
from repro.core.dse_api import DSEMethod, GANDSE, summarize
from repro.core.explorer import ExplorerConfig
from repro.core.gan import GANConfig
from repro.dataset.generator import generate_dataset, generate_tasks
from repro.design_models.dnnweaver import DnnWeaverModel
from repro.design_models.im2col import Im2colModel
from repro.design_models.tpu_mesh import TpuMeshModel

MODELS = {
    "dnnweaver": DnnWeaverModel,
    "im2col": Im2colModel,
    "tpu_mesh": TpuMeshModel,
}

#: Per-design-model exploration threshold (a deployment knob, §7.1.3:
#: higher-dimension/higher-entropy spaces need a sharper cut or the
#: candidate budget explodes) and training length (the tpu_mesh feasibility
#: structure, divisibility, HBM capacity and the expert axis, needs more
#: epochs to concentrate at CPU scale; about 11% of its rows are feasible).
MODEL_PRESETS = {
    "dnnweaver": dict(threshold=0.2, iters_mult=1, data_mult=1),
    "im2col": dict(threshold=0.3, iters_mult=1, data_mult=1),
    "tpu_mesh": dict(threshold=0.4, iters_mult=6, data_mult=2),
}

RESULTS_DIR = os.environ.get("REPRO_RESULTS", "results")


@dataclasses.dataclass(frozen=True)
class Scale:
    """Experiment scale (env-overridable, like benchmarks/common.py)."""

    n_data: int = int(os.environ.get("REPRO_GAN_DATA", 8000))
    n_tasks: int = int(os.environ.get("REPRO_GAN_TASKS", 200))
    iters: int = int(os.environ.get("REPRO_GAN_ITERS", 8))
    layers: int = int(os.environ.get("REPRO_GAN_LAYERS", 3))
    neurons: int = int(os.environ.get("REPRO_GAN_NEURONS", 256))
    lr: float = float(os.environ.get("REPRO_GAN_LR", 1e-4))
    w_critic: float = 0.5
    #: Pareto-adjacent objectives (§7.4 "hard" setting).  This is both the
    #: regime the paper's headline claim targets and the training
    #: distribution itself (dataset rows pair each witness config with its
    #: own exact metrics); loose slack hands budget-matched random search a
    #: dense satisfying region that masks conditioning quality entirely.
    slack: Tuple[float, float] = (1.0, 1.0)

    @staticmethod
    def quick() -> "Scale":
        """Smoke scale (tier-1 / CI): fewer tasks.  The GAN stays at the
        standing reduced scale (3x256) — an undertrained G inflates its own
        candidate budget, which hands budget-matched random search enough
        lottery tickets to mask real regressions in the comparison."""
        return Scale(n_tasks=50)


def build_methods(model, scale: Scale) -> List[DSEMethod]:
    """Every method of the comparison, untrained.  RandomSearch comes last
    so its budget can be matched to GANDSE's measured candidate count."""
    threshold = MODEL_PRESETS[model.name]["threshold"]
    explorer_cfg = ExplorerConfig(prob_threshold=threshold)
    gan_cfg = GANConfig(n_net=model.net_space.n_dims,
                        w_critic=scale.w_critic).scaled(
        layers=scale.layers, neurons=scale.neurons, lr=scale.lr,
        batch_size=512)
    return [
        GANDSE(model, gan_cfg, explorer_cfg),
        # parameter-matched to GAN G+D: ~2x layers at the same width, and
        # the same exploration threshold as G (fair thresholded outputs)
        LargeMLP(model, hidden_layers=2 * scale.layers,
                 neurons=scale.neurons, lr=scale.lr,
                 explorer_cfg=explorer_cfg),
        PolicyGradientDRL(model),
        SimulatedAnnealing(model),
        RandomSearch(model),
    ]


def run_comparison(model_name: str, scale: Optional[Scale] = None,
                   seed: int = 0, results_dir: str = RESULTS_DIR) -> Dict:
    """Train all methods on one shared dataset, explore one shared task
    set, and emit the Table-5-style rows for `model_name`."""
    scale = scale or Scale()
    model = MODELS[model_name]()
    preset = MODEL_PRESETS[model_name]
    ds = generate_dataset(model, scale.n_data * preset["data_mult"],
                          seed=seed)
    tasks = generate_tasks(model, scale.n_tasks, seed=seed + 1,
                           slack=scale.slack)

    rows = []
    gandse_budget = None
    for method in build_methods(model, scale):
        if method.method_name == "RandomSearch" and gandse_budget:
            method.n_samples = gandse_budget        # equal candidate budget
        t0 = time.time()
        iters = scale.iters * preset["iters_mult"]
        # DRL needs more iterations per unit progress: one iter = one
        # policy-gradient rollout batch, not one dataset epoch
        if method.method_name == "DRL":
            iters *= 4
        method.train(n_data=scale.n_data, iters=iters, seed=seed, ds=ds)
        train_s = time.time() - t0
        # warmup pass compiles every route so the timed run reports warm
        # serving time, not one-off XLA compiles amortized over the batch
        # (deterministic: same seed -> identical selections)
        method.explore_tasks(tasks, seed=seed + 2)
        results = method.explore_tasks(tasks, seed=seed + 2)
        row = summarize(results)
        row.update(
            method=method.method_name,
            train_time_s=round(train_s, 2),
            satisfied_rate=row["n_satisfied"] / max(row["n_tasks"], 1),
        )
        rows.append(row)
        if method.method_name == "GANDSE":
            gandse_budget = max(1, int(round(row["n_candidates"])))
        print(f"[comparison:{model_name}] {row['method']:12s} "
              f"sat={row['n_satisfied']}/{row['n_tasks']} "
              f"impr={row['improvement_ratio']:.4f} "
              f"dse={row['dse_time_s']*1e3:.2f}ms "
              f"cand={row['n_candidates']:.1f} train={train_s:.1f}s",
              flush=True)

    report = {
        "model": model_name,
        "scale": dataclasses.asdict(scale),
        "seed": seed,
        "rows": rows,
    }
    os.makedirs(results_dir, exist_ok=True)
    with open(os.path.join(results_dir, f"comparison_{model_name}.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", nargs="+", default=sorted(MODELS),
                    choices=sorted(MODELS))
    ap.add_argument("--quick", action="store_true",
                    help="smoke scale: fewer tasks (CI); nets and dataset "
                         "stay at the full reduced scale (see Scale.quick)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    scale = Scale.quick() if args.quick else Scale()

    combined = {}
    for name in args.models:
        combined[name] = run_comparison(name, scale, seed=args.seed)
    with open(os.path.join(RESULTS_DIR, "comparison.json"), "w") as f:
        json.dump(combined, f, indent=1)

    # the acceptance bar of the reproduction: GANDSE finds at least as many
    # satisfying designs as budget-matched random search, on every model
    ok = True
    for name, report in combined.items():
        by = {r["method"]: r for r in report["rows"]}
        g, r = by["GANDSE"], by["RandomSearch"]
        verdict = "ok" if g["satisfied_rate"] >= r["satisfied_rate"] else "FAIL"
        if verdict == "FAIL":
            ok = False
        print(f"[comparison:{name}] GANDSE {g['satisfied_rate']:.2f} vs "
              f"RandomSearch {r['satisfied_rate']:.2f} "
              f"(budget {r['n_candidates']:.0f}) -> {verdict}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
