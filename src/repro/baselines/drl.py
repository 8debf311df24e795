"""Deep-reinforcement-learning DSE baseline (paper §7.1.4, ConfuciuX-style).

Policy-gradient (REINFORCE with a moving baseline).  The state is the
current (network parameters, objectives, configuration); actions set one
configuration dimension to one of its choices; the reward is the decrease
in objective violation, with a bonus when the state satisfies the
objectives.  An MLP actor is trained offline over dataset-derived tasks;
at DSE time a short greedy rollout is run and the best visited
configuration is returned (iterative DSE, but with a learned policy).

Violations are clipped to ``VIOL_CLIP`` per metric: infeasible configs used
to map to ~1e9 violations whose one-step rewards swamped the moving
baseline and the advantage normalization.

DSE-time rollouts have two routes:

- **device** (default when the model has a jnp oracle): the whole rollout
  (policy forward -> action -> ``DesignModel.evaluate_jax`` scoring) is one
  jitted ``lax.scan`` vmapped over the task batch — ONE dispatch chain for
  T tasks instead of (rollout_len x T) host oracle calls.  Lane t draws
  from PRNGKey(seed + t), so a batched lane is bitwise-equal to the
  single-task device run with seed + t; winners are re-scored once by the
  float64 host oracle (the fused select's host-tail rule).
- **host** (fallback for models without a jnp oracle, and the sequential
  ``explore_tasks(batched=False)`` loop): the original numpy loop.
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
from repro.core.train import encode_batch
from repro.dataset.generator import Dataset, DSETask, generate_dataset
from repro.design_models.base import DesignModel
from repro.nn import layers as L
from repro.optim import adam, apply_updates

#: per-metric violation cap: bounds any one-step reward to
#: 2 * VIOL_CLIP + sat_bonus regardless of how infeasible a config is
VIOL_CLIP = 10.0


def _violation(lat, pw, lo, po):
    """Relative objective violation, each metric's term clipped to
    VIOL_CLIP (NaN/inf metrics saturate at the clip, not at ~1e9)."""
    lat = np.where(np.isnan(lat), np.inf, np.asarray(lat, np.float64))
    pw = np.where(np.isnan(pw), np.inf, np.asarray(pw, np.float64))
    lv = np.minimum(np.maximum(0.0, (lat - lo) / lo), VIOL_CLIP)
    pv = np.minimum(np.maximum(0.0, (pw - po) / po), VIOL_CLIP)
    return lv + pv


def _drl_rollout_kernel(model: DesignModel, rollout_len: int,
                        explore_eps: float):
    """Jitted vmapped DSE rollout: (params, net_idx (T,), net_enc, obj_enc,
    lo (T,), po (T,), keys (T,2)) -> (best cfg (T, n_dims), n_eval)."""
    space = model.space
    n_dims = space.n_dims
    sizes = jnp.asarray(space.group_sizes, jnp.int32)
    offs = np.concatenate([[0], np.cumsum(space.group_sizes)])
    starts = jnp.asarray(offs[:-1], jnp.int32)
    ends = jnp.asarray(offs[1:], jnp.int32)
    n_actions = space.onehot_width

    def onehot(cfg):
        return jnp.concatenate(
            [jax.nn.one_hot(cfg[i], d.n) for i, d in enumerate(space.dims)])

    def apply_action(cfg, a):
        di = jnp.searchsorted(ends, a, side="right")   # a's group
        return cfg.at[di].set(a - starts[di])

    def score(net_idx, cfg, lo, po):
        lat, pw = model.evaluate_jax_indices(net_idx[None, :], cfg[None, :])
        lat = jnp.where(jnp.isnan(lat[0]), jnp.inf, lat[0]).astype(jnp.float32)
        pw = jnp.where(jnp.isnan(pw[0]), jnp.inf, pw[0]).astype(jnp.float32)
        lv = jnp.minimum(jnp.maximum(0.0, (lat - lo) / lo), VIOL_CLIP)
        pv = jnp.minimum(jnp.maximum(0.0, (pw - po) / po), VIOL_CLIP)
        return lat, pw, lv + pv

    def one_task(params, net_idx, net_enc, obj_enc, lo, po, key):
        key, k0 = jax.random.split(key)
        cfg = jnp.floor(
            jax.random.uniform(k0, (n_dims,)) * sizes).astype(jnp.int32)
        lat0, pw0, v0 = score(net_idx, cfg, lo, po)

        def step(carry, t):
            key, cfg, best, best_l, best_p, best_v = carry
            x = jnp.concatenate([net_enc, obj_enc, onehot(cfg)])
            logits = L.mlp_apply(params, x[None])[0]
            key, ke, ka = jax.random.split(key, 3)
            a = jnp.where(
                (t > 0) & (jax.random.uniform(ke) < explore_eps),
                jax.random.randint(ka, (), 0, n_actions),
                jnp.argmax(logits).astype(jnp.int32))   # greedy at DSE time
            cfg = apply_action(cfg, a.astype(jnp.int32))
            lat, pw, v = score(net_idx, cfg, lo, po)
            improved = (v < best_v) | (
                (v == best_v) & jnp.isfinite(lat)
                & (lat + pw < best_l + best_p))
            best = jnp.where(improved, cfg, best)
            best_l = jnp.where(improved, lat, best_l)
            best_p = jnp.where(improved, pw, best_p)
            best_v = jnp.where(improved, v, best_v)
            return (key, cfg, best, best_l, best_p, best_v), None

        carry = (key, cfg, cfg, lat0, pw0, v0)
        (_, _, best, _, _, _), _ = jax.lax.scan(
            step, carry, jnp.arange(rollout_len))
        return best

    return jax.jit(jax.vmap(one_task,
                            in_axes=(None, 0, 0, 0, 0, 0, 0)))


@dataclasses.dataclass
class PolicyGradientDRL:
    model: DesignModel
    hidden_layers: int = 3
    neurons: int = 256
    lr: float = 1e-4
    rollout_len: int = 16
    batch_tasks: int = 64
    gamma: float = 0.95
    sat_bonus: float = 2.0
    explore_eps: float = 0.3
    seed: int = 0

    method_name = "DRL"

    def __post_init__(self):
        self.ds: Optional[Dataset] = None
        self.params = None
        space = self.model.space
        self._n_actions = space.onehot_width  # action = (dim, choice) flattened

        @jax.jit
        def policy_logits(params, net_enc, obj_enc, cfg_onehot):
            x = jnp.concatenate([net_enc, obj_enc, cfg_onehot], axis=-1)
            return L.mlp_apply(params, x)

        self._logits = policy_logits

    # --- helpers -------------------------------------------------------------
    def _apply_actions(self, cfg_idx: np.ndarray, actions: np.ndarray) -> np.ndarray:
        """actions: flat indices into onehot_width -> set (dim, choice)."""
        space = self.model.space
        out = cfg_idx.copy()
        off = 0
        for di, d in enumerate(space.dims):
            in_group = (actions >= off) & (actions < off + d.n)
            out[in_group, di] = actions[in_group] - off
            off += d.n
        return out

    def _rollout_kernel(self):
        key = (self.rollout_len, self.explore_eps)
        kernels = self.model.__dict__.setdefault("_drl_kernels", {})
        if key not in kernels:
            kernels[key] = _drl_rollout_kernel(self.model, self.rollout_len,
                                               self.explore_eps)
        return kernels[key]

    def attach(self, ds: Dataset, params) -> "PolicyGradientDRL":
        """Serving entry (mirrors GANDSE.attach): wire a dataset (for its
        normalizers) and trained policy params without retraining."""
        self.ds = ds
        self.params = params
        return self

    def init_params(self, seed: int = 0):
        """Fresh policy params — the single definition of the input width
        (net params + 2 objective channels + config one-hot), shared by
        `train` and the bench/serving `attach` path."""
        n_in = self.model.net_space.n_dims + 2 + self.model.space.onehot_width
        return L.mlp_init(jax.random.PRNGKey(seed), n_in,
                          [self.neurons] * self.hidden_layers,
                          self._n_actions)

    def train(self, n_data: int, iters: int, seed: int = 0,
              ds: Optional[Dataset] = None, log_every: int = 0):
        self.ds = ds if ds is not None else generate_dataset(self.model, n_data, seed=seed)
        space = self.model.space
        self.params = self.init_params(seed)
        optim = adam(self.lr)
        opt = optim.init(self.params)

        def pg_loss(params, states, actions, advantages):
            logits = self._logits(params, *states)
            logp = jax.nn.log_softmax(logits, axis=-1)
            act_logp = jnp.take_along_axis(logp, actions[:, None], axis=-1)[:, 0]
            return -jnp.mean(act_logp * advantages)

        @jax.jit
        def update(params, opt, states, actions, advantages):
            loss, grads = jax.value_and_grad(pg_loss)(params, states, actions, advantages)
            upd, opt = optim.update(grads, opt)
            return apply_updates(params, upd), opt, loss

        np_rng = np.random.default_rng(seed)
        baseline = 0.0
        for it in range(iters):
            # sample a batch of tasks from the dataset rows
            rows = np_rng.integers(0, self.ds.n, self.batch_tasks)
            b = encode_batch(self.model, self.ds, rows)
            net_idx = b["net_idx"]
            lo, po = b["lat_obj"], b["pow_obj"]
            cfg = space.sample_indices(np_rng, self.batch_tasks)
            lat, pw = self.model.evaluate_indices(net_idx, cfg)
            viol = _violation(lat, pw, lo, po)

            traj_states, traj_actions, traj_rewards = [], [], []
            for t in range(self.rollout_len):
                cfg_oh = space.onehot_from_indices(cfg)
                states = (jnp.asarray(b["net_enc"]), jnp.asarray(b["obj_enc"]),
                          jnp.asarray(cfg_oh))
                logits = np.asarray(self._logits(self.params, *states))
                # sample actions
                z = np_rng.gumbel(size=logits.shape)
                actions = np.argmax(logits + z, axis=-1).astype(np.int64)
                new_cfg = self._apply_actions(cfg, actions)
                lat, pw = self.model.evaluate_indices(net_idx, new_cfg)
                new_viol = _violation(lat, pw, lo, po)
                reward = (viol - new_viol) + self.sat_bonus * (new_viol == 0.0)
                traj_states.append(states)
                traj_actions.append(actions)
                traj_rewards.append(reward)
                cfg, viol = new_cfg, new_viol

            # discounted returns
            ret = np.zeros_like(traj_rewards[0])
            all_s, all_a, all_adv = [], [], []
            for t in reversed(range(self.rollout_len)):
                ret = traj_rewards[t] + self.gamma * ret
                all_s.append(traj_states[t])
                all_a.append(traj_actions[t])
                all_adv.append(ret.copy())
            adv = np.concatenate(all_adv)
            baseline = 0.9 * baseline + 0.1 * float(adv.mean())
            adv = (adv - baseline) / (adv.std() + 1e-6)
            states = tuple(jnp.concatenate([s[i] for s in all_s]) for i in range(3))
            actions = jnp.asarray(np.concatenate(all_a))
            self.params, opt, loss = update(self.params, opt, states, actions,
                                            jnp.asarray(adv, jnp.float32))
            if log_every and it % log_every == 0:
                print(f"[drl] iter={it} loss={float(loss):.4f} "
                      f"final_viol={viol.mean():.4f} sat={(viol == 0).mean():.3f}")
        return self

    # --- device route -------------------------------------------------------
    def _explore_device(self, tasks: DSETask, seed: int) -> List[DSEResult]:
        n_tasks = int(tasks.net_idx.shape[0])
        t0 = time.time()
        # rollout lanes shard over the active task mesh (pad, run, discard
        # padded lanes) — the policy params stay replicated (in_axes=None)
        seeds = row_seeds(seed, n_tasks)
        tasks_p, seeds, n_tasks = shard.pad_tasks(tasks, seeds)
        n_pad = int(tasks_p.net_idx.shape[0])
        net_enc = self.ds.net_encoded(self.model, tasks_p.net_idx)
        obj_enc = self.ds.obj_encoded(tasks_p.lat_obj, tasks_p.pow_obj)
        best = np.asarray(self._rollout_kernel()(
            shard.replicate(self.params),
            shard.put_sharded(np.asarray(tasks_p.net_idx, np.int32)),
            shard.put_sharded(net_enc), shard.put_sharded(obj_enc),
            shard.put_sharded(np.asarray(tasks_p.lat_obj, np.float32)),
            shard.put_sharded(np.asarray(tasks_p.pow_obj, np.float32)),
            shard.put_sharded(task_keys(seeds, n_pad))))[:n_tasks]
        # one float64 host-oracle call re-scores every winner
        lat64, pw64 = self.model.evaluate_indices(tasks.net_idx, best)
        per_task = (time.time() - t0) / n_tasks
        out = []
        for t in range(n_tasks):
            lo, po = float(tasks.lat_obj[t]), float(tasks.pow_obj[t])
            bl, bp = float(lat64[t]), float(pw64[t])
            sel = Selection(cfg_idx=best[t].copy(), latency=bl, power=bp,
                            satisfied=is_satisfied(bl, bp, lo, po),
                            n_candidates=self.rollout_len + 1)
            out.append(DSEResult(sel, lo, po, per_task))
        return out

    # --- host route ---------------------------------------------------------
    def _explore_host(self, net_idx: np.ndarray, lat_obj: float,
                      pow_obj: float, seed: int) -> DSEResult:
        t0 = time.time()
        space = self.model.space
        rng = np.random.default_rng(seed)
        lo, po = float(lat_obj), float(pow_obj)
        net_enc = self.ds.net_encoded(self.model, np.atleast_2d(net_idx))
        obj_enc = self.ds.obj_encoded([lo], [po])
        cfg = space.sample_indices(rng, 1)
        lat, pw = self.model.evaluate_indices(net_idx[None], cfg)
        best = (cfg[0].copy(), float(lat[0]), float(pw[0]),
                float(_violation(lat, pw, lo, po)[0]))
        n_eval = 1
        for t in range(self.rollout_len):
            cfg_oh = space.onehot_from_indices(cfg)
            logits = np.asarray(self._logits(self.params, jnp.asarray(net_enc),
                                             jnp.asarray(obj_enc), jnp.asarray(cfg_oh)))
            actions = np.argmax(logits, axis=-1)  # greedy at DSE time
            if t > 0 and rng.random() < self.explore_eps:  # light exploration
                actions = np.array([rng.integers(0, self._n_actions)])
            cfg = self._apply_actions(cfg, actions)
            lat, pw = self.model.evaluate_indices(net_idx[None], cfg)
            n_eval += 1
            v = float(_violation(lat, pw, lo, po)[0])
            l_, p_ = float(lat[0]), float(pw[0])
            if v < best[3] or (v == best[3] and np.isfinite(l_) and l_ + p_ < best[1] + best[2]):
                best = (cfg[0].copy(), l_, p_, v)
        c, bl, bp, bv = best
        sel = Selection(cfg_idx=c, latency=bl, power=bp,
                        satisfied=is_satisfied(bl, bp, lo, po),
                        n_candidates=n_eval)
        return DSEResult(sel, lo, po, time.time() - t0)

    # --- public API ---------------------------------------------------------
    def explore(self, net_idx: np.ndarray, lat_obj: float, pow_obj: float,
                seed: int = 0) -> DSEResult:
        # a model without a jnp oracle takes the host route (the GANDSE
        # fallback rule)
        if self.model.has_jax_oracle:
            tasks = DSETask.single(net_idx, lat_obj, pow_obj)
            return self._explore_device(tasks, seed)[0]
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
