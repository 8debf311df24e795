"""Random-search DSE baseline (sanity floor, not in the paper's table).

Uniformly samples N configurations and applies the Algorithm 2 selector.
Useful as the weakest-reasonable baseline and in property tests (any
learned method should beat it at equal evaluation budget).

``explore_tasks`` runs the tasks one after another through ``explore``:
task t samples from ``default_rng(seed + t)`` (or ``seed[t]``), and
``select`` picks its route from the candidate count.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import numpy as np

from repro.core.selector import select
from repro.core.dse_api import DSEResult, row_seeds
from repro.dataset.generator import Dataset, DSETask
from repro.design_models.base import DesignModel


@dataclasses.dataclass
class RandomSearch:
    model: DesignModel
    n_samples: int = 256

    method_name = "RandomSearch"

    def train(self, n_data: int = 0, iters: int = 0, seed: int = 0,
              ds: Optional[Dataset] = None, log_every: int = 0):
        """Random search is model-free — training is a no-op (DSEMethod
        protocol)."""
        return self

    def _candidates(self, seed: int) -> np.ndarray:
        rng = np.random.default_rng(seed)
        return self.model.space.sample_indices(rng, self.n_samples)

    def explore(self, net_idx: np.ndarray, lat_obj: float, pow_obj: float,
                seed: int = 0) -> DSEResult:
        t0 = time.time()
        cands = self._candidates(seed)
        sel = select(self.model, net_idx, cands, lat_obj, pow_obj)
        return DSEResult(sel, float(lat_obj), float(pow_obj), time.time() - t0)

    def explore_tasks(self, tasks: DSETask, seed: int = 0,
                      batched: Optional[bool] = None) -> List[DSEResult]:
        """The per-task route for every batch: ``batched`` is accepted for
        the DSEMethod protocol and changes nothing."""
        seeds = row_seeds(seed, int(tasks.net_idx.shape[0]))
        return [self.explore(tasks.net_idx[i], tasks.lat_obj[i],
                             tasks.pow_obj[i], seed=int(seeds[i]))
                for i in range(len(seeds))]
