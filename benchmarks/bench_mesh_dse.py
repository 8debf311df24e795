"""Beyond-paper: GAN-DSE over the TPU-mesh design space vs exhaustive
search (the space is small enough to enumerate, giving exact regret)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import write_json
from repro.core.dse_api import GANDSE
from repro.core.gan import GANConfig
from repro.dataset.generator import generate_tasks
from repro.design_models.tpu_mesh import TpuMeshModel


def exhaustive_best(model, net_idx, lo, po):
    space = model.space
    # enumerate the whole mesh space (235,200 configs)
    idx = np.indices([d.n for d in space.dims]).reshape(space.n_dims, -1).T
    net = np.repeat(net_idx[None], idx.shape[0], axis=0)
    lat, pw = model.evaluate_indices(net, idx)
    ok = (lat <= lo) & (pw <= po)
    if not ok.any():
        return None
    j = np.flatnonzero(ok)
    best = j[np.argmin(lat[j] / lo + pw[j] / po)]
    return float(lat[best]), float(pw[best])


def run(n_tasks=40) -> dict:
    model = TpuMeshModel()
    cfg = GANConfig(n_net=model.net_space.n_dims, w_critic=1.0).scaled(
        layers=3, neurons=256, batch_size=512, lr=1e-4)
    g = GANDSE(model, cfg)
    t0 = time.time()
    g.train(n_data=8000, iters=8, seed=0)
    t_train = time.time() - t0

    tasks = generate_tasks(model, n_tasks, seed=2, slack=(1.1, 2.0))
    res = g.explore_tasks(tasks)
    sat, regret = 0, []
    possible = 0
    for i, r in enumerate(res):
        ex = exhaustive_best(model, tasks.net_idx[i], tasks.lat_obj[i],
                             tasks.pow_obj[i])
        if ex is None:
            continue
        possible += 1
        if r.satisfied:
            sat += 1
            regret.append(r.selection.latency / max(ex[0], 1e-12))
    out = {
        "train_time_s": t_train,
        "tasks_satisfiable": possible,
        "gan_satisfied": sat,
        "mean_latency_vs_exhaustive": float(np.mean(regret)) if regret else None,
        "dse_time_s": float(np.mean([r.dse_seconds for r in res])),
    }
    print(f"[mesh_dse] sat={sat}/{possible} "
          f"latency_vs_exhaustive={out['mean_latency_vs_exhaustive']} "
          f"dse={out['dse_time_s']*1e3:.0f}ms", flush=True)
    write_json("mesh_dse.json", out)
    return out


def main():
    run()


if __name__ == "__main__":
    main()
