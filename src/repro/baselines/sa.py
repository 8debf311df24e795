"""Simulated annealing DSE baseline (paper §7.1.4).

Iterative DSE in the classic Fig. 1 loop: the configuration-updating
algorithm is SA over the discrete choice indices; the design model scores
each visited configuration.  "SA terminates once the user's objectives are
satisfied, or the temperature is 3e-8 x the initial one."

Two routes share the annealing schedule:

- **device** (default when the model has a jnp oracle): the whole anneal is
  one jitted ``lax.while_loop`` — propose / score via
  ``DesignModel.evaluate_jax`` / accept — vmapped over the task batch, so a
  batch costs ONE dispatch instead of one host oracle call per visited
  config.  Tasks whose best violation hits zero freeze (the batched while
  keeps them fixed), matching the sequential early exit, so lane t is
  bitwise-equal to a single-task device run with seed + t.
- **host** (fallback for models without a jnp oracle, and the sequential
  ``explore_tasks(batched=False)`` loop): the original numpy loop with
  one ``evaluate_indices`` call per step.

Winners from the device route are re-scored once with the float64 host
oracle so reported metrics stay precision-consistent with the host route
(the same rule as the fused select's host tail).
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import shard
from repro.core.explorer import task_keys
from repro.core.selector import Selection, is_satisfied
from repro.core.dse_api import DSEResult, row_seeds
from repro.dataset.generator import Dataset, DSETask
from repro.design_models.base import DesignModel

#: violation assigned to infeasible (non-finite metric) configurations
_BIG = 1e9


def _violation(lat, pw, lo, po):
    """Objective violation; non-finite (inf/NaN) metrics -> _BIG.

    Both metrics must be guarded: a finite-latency/non-finite-power config
    otherwise yields inf/NaN energies whose comparisons silently corrupt
    the accept/best logic (a NaN power even counts as zero violation, i.e.
    "satisfied").
    """
    if not (np.isfinite(lat) and np.isfinite(pw)):
        return _BIG
    return max(0.0, (lat - lo) / lo) + max(0.0, (pw - po) / po)


def _sa_device_kernel(model: DesignModel, t_init: float, cooling: float,
                      steps_per_temp: int, max_steps: int):
    """Jitted vmapped anneal: (net_idx (T,), lo (T,), po (T,), keys (T,2))
    -> (best cfg (T, n_dims), best violation (T,), n_eval (T,))."""
    space = model.space
    n_dims = space.n_dims
    sizes = jnp.asarray(space.group_sizes, jnp.int32)

    def viol(lat, pw, lo, po):
        lat = lat.astype(jnp.float32)
        pw = pw.astype(jnp.float32)
        v = (jnp.maximum(0.0, (lat - lo) / lo)
             + jnp.maximum(0.0, (pw - po) / po))
        return jnp.where(jnp.isfinite(lat) & jnp.isfinite(pw), v,
                         jnp.float32(_BIG))

    def score(net_idx, cfg, lo, po):
        lat, pw = model.evaluate_jax_indices(net_idx[None, :], cfg[None, :])
        return lat[0], pw[0], viol(lat[0], pw[0], lo, po)

    def one_task(net_idx, lo, po, key):
        key, k0 = jax.random.split(key)
        cur = jnp.floor(
            jax.random.uniform(k0, (n_dims,)) * sizes).astype(jnp.int32)
        lat0, pw0, e0 = score(net_idx, cur, lo, po)

        def cond(c):
            (_, _, _, _, _, _, best_e, _, step) = c
            return (step < max_steps) & (best_e > 0.0)

        def body(c):
            key, cur, cur_e, best, best_l, best_p, best_e, n_eval, step = c
            temp = t_init * jnp.power(
                jnp.float32(cooling), (step // steps_per_temp).astype(jnp.float32))
            key, kd, km, ks, kr, ka = jax.random.split(key, 6)
            d = jax.random.randint(kd, (), 0, n_dims)
            nd = sizes[d]
            local = jnp.where(
                jax.random.uniform(ks) < 0.5, -1, 1) + cur[d]   # +-1 move
            local = jnp.clip(local, 0, nd - 1)
            redraw = jnp.floor(jax.random.uniform(kr) * nd).astype(jnp.int32)
            nxt = cur.at[d].set(
                jnp.where(jax.random.uniform(km) < 0.5, local, redraw))
            lat, pw, e = score(net_idx, nxt, lo, po)
            accept = (e < cur_e) | (
                jax.random.uniform(ka)
                < jnp.exp(-(e - cur_e) / jnp.maximum(temp, 1e-12)))
            cur = jnp.where(accept, nxt, cur)
            cur_e = jnp.where(accept, e, cur_e)
            improved = accept & (
                (e < best_e)
                | ((e == best_e) & (lat + pw < best_l + best_p)))
            best = jnp.where(improved, nxt, best)
            best_l = jnp.where(improved, lat, best_l)
            best_p = jnp.where(improved, pw, best_p)
            best_e = jnp.where(improved, e, best_e)
            return (key, cur, cur_e, best, best_l, best_p, best_e,
                    n_eval + 1, step + 1)

        carry = (key, cur, e0, cur, lat0.astype(jnp.float32),
                 pw0.astype(jnp.float32), e0, jnp.int32(1), jnp.int32(0))
        (_, _, _, best, _, _, best_e, n_eval, _) = jax.lax.while_loop(
            cond, body, carry)
        return best, best_e, n_eval

    return jax.jit(jax.vmap(one_task))


@dataclasses.dataclass
class SimulatedAnnealing:
    model: DesignModel
    t_init: float = 1.0
    t_stop_frac: float = 3e-8
    cooling: float = 0.95
    steps_per_temp: int = 4
    seed: int = 0

    method_name = "SA"

    def train(self, n_data: int = 0, iters: int = 0, seed: int = 0,
              ds: Optional[Dataset] = None, log_every: int = 0):
        """SA is model-free — training is a no-op (DSEMethod protocol)."""
        return self

    @property
    def max_steps(self) -> int:
        """Proposal budget of one anneal: temperatures until the stop
        fraction, times steps per temperature (same count as the host
        while loop)."""
        n_temps = int(np.ceil(np.log(self.t_stop_frac) / np.log(self.cooling)))
        return n_temps * self.steps_per_temp

    def _kernel(self):
        key = (self.t_init, self.cooling, self.steps_per_temp, self.max_steps)
        kernels = self.model.__dict__.setdefault("_sa_kernels", {})
        if key not in kernels:
            kernels[key] = _sa_device_kernel(self.model, self.t_init,
                                             self.cooling,
                                             self.steps_per_temp,
                                             self.max_steps)
        return kernels[key]

    # --- device route -------------------------------------------------------
    def _explore_device(self, tasks: DSETask, seed: int) -> List[DSEResult]:
        n_tasks = int(tasks.net_idx.shape[0])
        t0 = time.time()
        # under an active task mesh the anneal lanes shard over the mesh's
        # batch axes (pad to the shard multiple, discard padded lanes) —
        # same jitted while_loop, same per-lane streams, same Selections
        seeds = row_seeds(seed, n_tasks)
        tasks_p, seeds, n_tasks = shard.pad_tasks(tasks, seeds)
        n_pad = int(tasks_p.net_idx.shape[0])
        best, best_e, n_eval = self._kernel()(
            shard.put_sharded(np.asarray(tasks_p.net_idx, np.int32)),
            shard.put_sharded(np.asarray(tasks_p.lat_obj, np.float32)),
            shard.put_sharded(np.asarray(tasks_p.pow_obj, np.float32)),
            shard.put_sharded(task_keys(seeds, n_pad)))
        best = np.asarray(best)[:n_tasks]
        n_eval = np.asarray(n_eval)[:n_tasks]
        # one float64 host-oracle call re-scores every winner (metrics and
        # `satisfied` stay precision-consistent with the host route)
        lat64, pw64 = self.model.evaluate_indices(tasks.net_idx, best)
        per_task = (time.time() - t0) / n_tasks
        out = []
        for t in range(n_tasks):
            lo, po = float(tasks.lat_obj[t]), float(tasks.pow_obj[t])
            bl, bp = float(lat64[t]), float(pw64[t])
            sel = Selection(cfg_idx=best[t].copy(), latency=bl, power=bp,
                            satisfied=is_satisfied(bl, bp, lo, po),
                            n_candidates=int(n_eval[t]))
            out.append(DSEResult(sel, lo, po, per_task))
        return out

    # --- host route ---------------------------------------------------------
    def _explore_host(self, net_idx: np.ndarray, lat_obj: float,
                      pow_obj: float, seed: Optional[int]) -> DSEResult:
        rng = np.random.default_rng(self.seed if seed is None else seed)
        space = self.model.space
        t0 = time.time()
        lo, po = float(lat_obj), float(pow_obj)

        cur = space.sample_indices(rng, 1)[0]
        lat, pw = self.model.evaluate_indices(net_idx[None], cur[None])
        cur_l, cur_p = float(lat[0]), float(pw[0])
        cur_e = _violation(cur_l, cur_p, lo, po)
        best = (cur.copy(), cur_l, cur_p, cur_e)
        n_eval = 1

        temp = self.t_init
        while temp > self.t_init * self.t_stop_frac and best[3] > 0.0:
            for _ in range(self.steps_per_temp):
                nxt = cur.copy()
                d = rng.integers(0, space.n_dims)
                if rng.random() < 0.5:  # local move
                    nxt[d] = int(np.clip(nxt[d] + rng.choice([-1, 1]), 0,
                                         space.dims[d].n - 1))
                else:                   # random re-draw
                    nxt[d] = rng.integers(0, space.dims[d].n)
                lat, pw = self.model.evaluate_indices(net_idx[None], nxt[None])
                n_eval += 1
                nl, np_ = float(lat[0]), float(pw[0])
                e = _violation(nl, np_, lo, po)
                if e < cur_e or rng.random() < np.exp(-(e - cur_e) / max(temp, 1e-12)):
                    cur, cur_l, cur_p, cur_e = nxt, nl, np_, e
                    if e < best[3] or (e == best[3] and nl + np_ < best[1] + best[2]):
                        best = (cur.copy(), nl, np_, e)
                if best[3] == 0.0:
                    break
            temp *= self.cooling

        cfg, bl, bp, be = best
        sel = Selection(cfg_idx=cfg, latency=bl, power=bp,
                        satisfied=is_satisfied(bl, bp, lo, po),
                        n_candidates=n_eval)
        return DSEResult(sel, lo, po, time.time() - t0)

    # --- public API ---------------------------------------------------------
    def explore(self, net_idx: np.ndarray, lat_obj: float, pow_obj: float,
                seed: Optional[int] = None) -> DSEResult:
        # a model without a jnp oracle takes the host route (the GANDSE
        # fallback rule)
        if self.model.has_jax_oracle:
            tasks = DSETask.single(net_idx, lat_obj, pow_obj)
            return self._explore_device(
                tasks, self.seed if seed is None else seed)[0]
        return self._explore_host(net_idx, lat_obj, pow_obj, seed)

    def explore_tasks(self, tasks: DSETask, seed: int = 0,
                      batched: Optional[bool] = None) -> List[DSEResult]:
        batched = self.model.has_jax_oracle and (batched is None or batched)
        n_tasks = int(tasks.net_idx.shape[0])
        if n_tasks == 0:
            return []
        if batched:
            return self._explore_device(tasks, seed)
        seeds = row_seeds(seed, n_tasks)
        return [self._explore_host(tasks.net_idx[i], tasks.lat_obj[i],
                                   tasks.pow_obj[i], int(seeds[i]))
                for i in range(n_tasks)]
