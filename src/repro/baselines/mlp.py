"""Large-MLP DSE baseline (paper §7.1.4, AIRCHITECT-style, Fig. 3(a)).

A single MLP regresses from (net params, objectives) to the training-set
configurations with plain per-group cross entropy — no satisfaction mask,
no discriminator.  Parameter count is matched to the full GAN (G + D) by
construction ("much larger than the G in the GAN").  The design selector
(Algorithm 2) is applied to its thresholded outputs, as in the paper.

Exploration mirrors the GANDSE explorer exactly: the MLP receives the same
noise input as G (§7.1.4), task t averages ``noise_samples`` forward passes
drawn from PRNGKey(seed + t), and ``explore_tasks`` serves the whole batch
device-resident (vmapped forward -> on-device candidate enumeration ->
batched Algorithm 2), falling back to the sequential host loop for models
without a jnp oracle.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gan as G
from repro.core import shard
from repro.core.explorer import (ExplorerConfig, enumerate_candidates,
                                 enumerate_candidates_batch,
                                 flatten_task_draws, task_keys)
from repro.core.fused_select import fused_select_batch
from repro.core.selector import select, select_batch
from repro.core.dse_api import DSEResult, row_seeds
from repro.core.train import encode_batch
from repro.dataset.generator import Dataset, DSETask, generate_dataset
from repro.design_models.base import DesignModel
from repro.nn import layers as L
from repro.optim import adam, apply_updates


@functools.lru_cache(maxsize=None)
def _cached_fwd(space, noise_dim: int, use_fused: Optional[bool] = None,
                chained: bool = None, mesh=None):
    """Jitted MLP inference, cached on (space, noise_dim, use_fused) like
    the explorer's G forward: retrains / new LargeMLP instances never
    recompile.

    ``fwd``: plain batch forward (training loss path; per-layer fused
    dense on the fused route so the loss stays differentiable).
    ``fwd_mean``: per-task noise-averaged forward for exploration — task t
    averages n_samples draws from fold_in(keys[t], s), the same streams
    whether tasks run one at a time or batched (the batched-vs-sequential
    parity contract, identical to the Explorer's).  On the fused route
    (``chained`` None = dispatch auto) the draws flatten into one row
    batch through the layer-chained megakernel, mirroring the Explorer.
    ``mesh``: the task mesh ``fwd_mean``'s inputs are sharded over (None =
    one device); the kernel route runs per shard on it.
    """
    from repro.kernels import dispatch as D
    if chained is None:
        chained = D.fused_enabled(use_fused) and D.on_tpu()

    def _probs_logits(logits):
        probs = [jax.nn.softmax(g, -1) for g in space.split_groups(logits)]
        return jnp.concatenate(probs, axis=-1)

    def _probs(params, net_enc, obj_enc, noise):
        x = jnp.concatenate([net_enc, obj_enc, noise], axis=-1)
        return _probs_logits(L.mlp_apply(params, x, use_fused=use_fused))

    fwd = jax.jit(_probs)

    def noise_fn(key, s):
        return G.sample_noise_dim(jax.random.fold_in(key, s), 1, noise_dim)[0]

    @functools.partial(jax.jit, static_argnames="n_samples")
    def fwd_mean(params, net_enc, obj_enc, keys, n_samples):
        if chained:
            t = net_enc.shape[0]
            net_r, obj_r, noise_r = flatten_task_draws(
                net_enc, obj_enc, keys, n_samples, noise_fn)
            x = jnp.concatenate([net_r, obj_r, noise_r], axis=-1)
            probs = _probs_logits(
                L.mlp_apply_chained(params, x, use_fused=use_fused,
                                    mesh=mesh))
            return jnp.mean(probs.reshape(t, n_samples, -1), axis=1)

        def one_task(net, obj, key):
            def one(s):
                noise = G.sample_noise_dim(jax.random.fold_in(key, s), 1,
                                           noise_dim)
                return _probs(params, net[None], obj[None], noise)[0]
            return jnp.mean(jax.vmap(one)(jnp.arange(n_samples)), axis=0)

        return jax.vmap(one_task)(net_enc, obj_enc, keys)

    return fwd, fwd_mean


@dataclasses.dataclass
class LargeMLP:
    model: DesignModel
    hidden_layers: int = 16           # parameter-matched to G+D
    neurons: int = 2048
    lr: float = 2e-5
    batch_size: int = 1024
    noise_dim: int = 8
    explorer_cfg: ExplorerConfig = dataclasses.field(default_factory=ExplorerConfig)
    #: Pallas fused-MLP path (kernels/dispatch.py rule): None = backend auto
    use_fused: Optional[bool] = None

    method_name = "LargeMLP"

    def __post_init__(self):
        self.ds: Optional[Dataset] = None
        self.params = None
        self._fwd = _cached_fwd(self.model.space, self.noise_dim,
                                self.use_fused)[0]

    def set_use_fused(self, use_fused: Optional[bool]) -> "LargeMLP":
        """Flip the fused-MLP dispatch (serving-layer override hook);
        refreshes the cached jitted forwards for the new route."""
        self.use_fused = use_fused
        self._fwd = _cached_fwd(self.model.space, self.noise_dim,
                                use_fused)[0]
        return self

    def n_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self.params))

    def init_params(self, seed: int = 0):
        """Fresh params for this architecture — the single definition of the
        input width (net params + 2 objective channels + noise), shared by
        `train` and the bench/serving `attach` path."""
        n_in = self.model.net_space.n_dims + 2 + self.noise_dim
        return L.mlp_init(jax.random.PRNGKey(seed), n_in,
                          [self.neurons] * self.hidden_layers,
                          self.model.space.onehot_width)

    def train(self, n_data: int, iters: int, seed: int = 0,
              ds: Optional[Dataset] = None, log_every: int = 0):
        self.ds = ds if ds is not None else generate_dataset(self.model, n_data, seed=seed)
        space = self.model.space
        rng = jax.random.PRNGKey(seed)
        self.params = self.init_params(seed)
        optim = adam(self.lr)
        opt = optim.init(self.params)

        def loss_fn(params, batch, noise):
            probs = self._fwd(params, batch["net_enc"], batch["obj_enc"], noise)
            return jnp.mean(G.grouped_cross_entropy(space, batch["cfg_onehot"], probs))

        @jax.jit
        def step(params, opt, batch, rng):
            rng, nrng = jax.random.split(rng)
            noise = G.sample_noise_dim(nrng, batch["net_enc"].shape[0],
                                       self.noise_dim)
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, noise)
            upd, opt = optim.update(grads, opt)
            return apply_updates(params, upd), opt, rng, loss

        np_rng = np.random.default_rng(seed)
        n = self.ds.n
        bs = min(self.batch_size, n)
        for it in range(iters):
            perm = np_rng.permutation(n)
            for b0 in range(0, n - bs + 1, bs):
                batch = {k: jnp.asarray(v) for k, v in
                         encode_batch(self.model, self.ds, perm[b0:b0 + bs]).items()}
                self.params, opt, rng, loss = step(self.params, opt, batch, rng)
            if log_every and it % log_every == 0:
                print(f"[large_mlp] iter={it} loss={float(loss):.4f}")
        return self

    def attach(self, ds: Dataset, params) -> "LargeMLP":
        """Serving entry (mirrors GANDSE.attach): wire a dataset (for its
        normalizers) and trained params without retraining."""
        self.ds = ds
        self.params = params
        return self

    def generator_probs_device(self, net_idx: np.ndarray, lat_obj, pow_obj,
                               seed: int = 0) -> jnp.ndarray:
        """Vmapped noise-averaged forward: (T, onehot_width) device probs.
        Task row t draws from PRNGKey(seed + t) (host-int64 sum), bitwise
        equal to a single-task call with seed + t."""
        net_enc = self.ds.net_encoded(self.model, np.atleast_2d(net_idx))
        obj_enc = self.ds.obj_encoded(np.atleast_1d(lat_obj),
                                      np.atleast_1d(pow_obj))
        keys = task_keys(seed, net_enc.shape[0])
        # task-sharded over the active mesh (passed through without one),
        # see repro.core.shard.put_sharded
        fwd_mean = _cached_fwd(self.model.space, self.noise_dim,
                               self.use_fused, mesh=shard.get_task_mesh())[1]
        return fwd_mean(self.params, shard.put_sharded(net_enc),
                              shard.put_sharded(obj_enc),
                              shard.put_sharded(keys),
                              n_samples=self.explorer_cfg.noise_samples)

    def explore(self, net_idx: np.ndarray, lat_obj: float, pow_obj: float,
                seed: int = 0) -> DSEResult:
        t0 = time.time()
        probs = np.asarray(
            self.generator_probs_device(net_idx, lat_obj, pow_obj, seed))[0]
        cands = enumerate_candidates(self.model.space, probs,
                                     self.explorer_cfg.prob_threshold,
                                     self.explorer_cfg.max_candidates)
        sel = select(self.model, net_idx, cands, lat_obj, pow_obj)
        return DSEResult(sel, float(lat_obj), float(pow_obj), time.time() - t0)

    def explore_batch(self, tasks: DSETask, seed: int = 0) -> List[DSEResult]:
        """Batched device-resident exploration, same structure (and parity
        contract) as ``GANDSE.explore_batch``: vmapped forward -> fused
        streaming enumerate/score/select (``batch_route="dense"`` on the
        explorer config keeps the reference materialized route).
        dse_seconds is the amortized per-task wall-clock."""
        n_tasks = int(tasks.net_idx.shape[0])
        if n_tasks == 0:
            return []
        if not self.model.has_jax_oracle:
            return self._explore_seq(tasks, seed)
        t0 = time.time()
        # pad to the active mesh's shard multiple (GANDSE.explore_batch
        # rule: padded lanes computed and discarded, parity bit-exact)
        seeds = row_seeds(seed, n_tasks)
        tasks_p, seeds, n_real = shard.pad_tasks(tasks, seeds)
        probs = self.generator_probs_device(tasks_p.net_idx, tasks_p.lat_obj,
                                            tasks_p.pow_obj, seeds)
        if self.explorer_cfg.batch_route == "dense":
            cand, valid, counts = enumerate_candidates_batch(
                self.model.space, probs, self.explorer_cfg.prob_threshold,
                self.explorer_cfg.max_candidates)
            sels = select_batch(self.model, tasks_p.net_idx, cand, valid,
                                counts, tasks_p.lat_obj, tasks_p.pow_obj)
        else:
            sels = fused_select_batch(
                self.model, tasks_p.net_idx, probs,
                self.explorer_cfg.prob_threshold,
                self.explorer_cfg.max_candidates,
                tasks_p.lat_obj, tasks_p.pow_obj,
                tile=self.explorer_cfg.select_tile)
        per_task = (time.time() - t0) / n_real
        return [
            DSEResult(sel, float(tasks.lat_obj[i]), float(tasks.pow_obj[i]),
                      per_task)
            for i, sel in enumerate(sels[:n_real])
        ]

    def explore_tasks(self, tasks: DSETask, seed: int = 0,
                      batched: Optional[bool] = None) -> List[DSEResult]:
        if batched is None:
            batched = self.model.has_jax_oracle
        if batched:
            return self.explore_batch(tasks, seed=seed)
        return self._explore_seq(tasks, seed)

    def _explore_seq(self, tasks: DSETask, seed) -> List[DSEResult]:
        seeds = row_seeds(seed, tasks.net_idx.shape[0])
        return [self.explore(tasks.net_idx[i], tasks.lat_obj[i], tasks.pow_obj[i],
                             seed=int(seeds[i]))
                for i in range(tasks.net_idx.shape[0])]
