"""Algorithm 1 — the proposed GAN training scheme.

For each sample s of a batch:
    Config_g <- G(Net_s, LO_s, PO_s)                 (line 5)
    Sat      <- D(Net_s, Config_g, LO_s, PO_s)       (line 6)
    L_g, P_g <- design model(Net_s, Config_g)        (lines 7-8)
    Loss_critic += E(Sat, True)/bs                   (line 9)
    if L_g <= LO_s and P_g <= PO_s:                  (line 10)
        Loss_config += 0;      Loss_dis += E(Sat, True)/bs
    else:
        Loss_config += E(Config_s, Config_g)/bs;  Loss_dis += E(Sat, False)/bs
    update G with Loss_config + w_critic * Loss_critic
    update D with Loss_dis

The design model is an *external, non-differentiable* oracle exactly as in
the paper (Fig. 3(c)): its output enters the losses only as constants
(labels / masks), never in the gradient path.  G's gradients flow through
D (frozen) for the critic term and through the per-group CE for the config
term.

Two oracle routes exist:

- **fused** (default for the built-in models): the design model's pure-jnp
  twin ``DesignModel.evaluate_jax`` is traced straight into the jitted
  step under ``stop_gradient`` — no host round-trip, so a whole epoch runs
  as one ``jax.lax.scan`` over device-resident batches.
- **callback** (fallback for models without a jnp port, e.g. external RTL
  simulators): ``jax.pure_callback`` to the host numpy ``evaluate``, as in
  the original implementation.

``train_gan`` pre-encodes the dataset once, uploads it once, and runs each
epoch as a single jitted scan with the (params, opt-state, rng) carry
donated — the Python interpreter touches the hot path once per epoch, not
once per batch.

On TPU the G/D MLP layers inside the step run through the Pallas fused
dense+bias+ReLU kernels — forward and backward (their custom_vjp) — per
the ``kernels/dispatch.py`` rule; ``GANConfig.use_fused`` overrides it.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gan as G
from repro.core import shard
from repro.core.encoding import binary_log2_encode
from repro.dataset.generator import Dataset
from repro.design_models.base import DesignModel
from repro.optim import adam, apply_updates
from repro.train.shardings import axis_size


@dataclasses.dataclass
class TrainState:
    g_params: dict
    d_params: dict
    g_opt: object
    d_opt: object
    rng: jax.Array
    history: List[Dict[str, float]] = dataclasses.field(default_factory=list)


def _design_model_callback(model: DesignModel):
    """Non-differentiable oracle: (B, n_dims) int indices -> (L, P) float32."""

    def eval_np(cfg_idx, net_idx):
        lat, pw = model.evaluate_indices(np.asarray(net_idx), np.asarray(cfg_idx))
        big = np.float32(3.4e38)
        # NaN (a broken oracle formula) counts as infeasible, not as 0.0
        # "satisfies everything" — mirrored by the fused route.
        lat = np.nan_to_num(lat.astype(np.float32), nan=big, posinf=big)
        pw = np.nan_to_num(pw.astype(np.float32), nan=big, posinf=big)
        return lat, pw

    return eval_np


def make_oracle(model: DesignModel, use_jax_oracle: Optional[bool] = None):
    """Build the in-step oracle: (cfg_idx, net_idx) -> (lat, pw) float32.

    use_jax_oracle: True forces the fused jnp route (raises if the model has
    no ``evaluate_jax``), False forces the host-callback route, None picks
    the fused route whenever the model provides it.  Returns (fn, fused).
    Infinite and NaN metrics are clamped to float32-max (i.e. treated as
    infeasible) so downstream comparisons against objectives stay
    well-defined and identical on both routes.
    """
    if use_jax_oracle is None:
        use_jax_oracle = model.has_jax_oracle
    if use_jax_oracle:
        if not model.has_jax_oracle:
            raise ValueError(f"model {model.name!r} has no jnp oracle")
        big = jnp.float32(3.4e38)

        def fused(cfg_idx, net_idx):
            lat, pw = model.evaluate_jax_indices(net_idx, cfg_idx)
            lat = jnp.nan_to_num(lat.astype(jnp.float32), nan=big, posinf=big)
            pw = jnp.nan_to_num(pw.astype(jnp.float32), nan=big, posinf=big)
            # Pin the oracle outputs as materialized buffers via an explicit
            # gather: XLA CPU's instruction fusion otherwise duplicates the
            # whole elementwise oracle chain into every consumer fusion —
            # in grad programs that re-evaluates the oracle once per
            # (row, one-hot column) of the CE backward and doubles the step
            # time.  Gathers are never re-fused, so this is a cheap barrier.
            iota = jnp.arange(lat.shape[0])
            return lat[iota], pw[iota]

        return fused, True

    host = _design_model_callback(model)

    def callback(cfg_idx, net_idx):
        out_spec = (
            jax.ShapeDtypeStruct((cfg_idx.shape[0],), jnp.float32),
            jax.ShapeDtypeStruct((cfg_idx.shape[0],), jnp.float32),
        )
        return jax.pure_callback(
            host, out_spec, cfg_idx, net_idx, vmap_method="sequential"
        )

    return callback, False


def _batch_constrainer(mesh):
    """Sharding constraint pinning each batch leaf's leading (sample) axis
    over the mesh's batch axes — the data-parallel layout of Algorithm 1.
    Identity when the mesh has no task axes (or None), so the unsharded
    trace is byte-identical to the pre-mesh one."""
    axes = shard.task_axes(mesh)
    if axes is None:
        return lambda batch: batch
    from jax.sharding import NamedSharding, PartitionSpec as P

    k = axis_size(mesh, axes)

    def constrain(batch):
        def pin(a):
            if a.ndim == 0 or a.shape[0] % k != 0:
                return a
            spec = [None] * a.ndim
            spec[0] = axes
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P(*spec)))
        return jax.tree.map(pin, batch)

    return constrain


def _make_step_body(model: DesignModel, cfg: G.GANConfig,
                    use_jax_oracle: Optional[bool] = None,
                    mesh=None):
    """The un-jitted Algorithm 1 update as a scan body over batches.

    Returns (g_optim, d_optim, step_body) where
    step_body(carry, batch) -> (carry, metrics) and
    carry = (g_params, d_params, g_opt, d_opt, rng).

    With a `mesh`, each batch is constrained sample-sharded over the
    mesh's batch axes inside the body: G/D forwards, the oracle, and both
    backward passes partition row-wise, and the batch-mean losses make
    GSPMD all-reduce the gradients over ('pod', 'data') — plain data
    parallelism, params replicated.
    """
    space = model.space
    oracle, _ = make_oracle(model, use_jax_oracle)
    constrain = _batch_constrainer(mesh)

    def losses_g(g_params, d_params, batch, noise):
        probs = G.generator_apply(g_params, space, batch["net_enc"],
                                  batch["obj_enc"], noise,
                                  use_fused=cfg.use_fused, mesh=mesh)
        # --- external design model on the hard-decoded config (lines 7-8)
        cfg_idx = G.decode_hard(space, probs)
        lat_g, pow_g = oracle(cfg_idx, batch["net_idx"])
        sat_actual = ((lat_g <= batch["lat_obj"]) & (pow_g <= batch["pow_obj"])).astype(jnp.float32)
        sat_actual = jax.lax.stop_gradient(sat_actual)

        # D is frozen here (grads are taken w.r.t. g_params only); gradients
        # flow *through* D into G's probs — that is the critic signal.
        sat_logits = G.discriminator_apply(d_params, batch["net_enc"], probs,
                                           batch["obj_enc"],
                                           use_fused=cfg.use_fused, mesh=mesh)
        loss_critic = jnp.mean(G.satisfaction_ce(sat_logits, jnp.ones_like(sat_actual)))
        ce_cfg = G.grouped_cross_entropy(space, batch["cfg_onehot"], probs)
        loss_config = jnp.mean((1.0 - sat_actual) * ce_cfg)       # masked (line 11/14)
        loss_g = loss_config + cfg.w_critic * loss_critic
        aux = dict(loss_config=loss_config, loss_critic=loss_critic,
                   probs=probs, sat_actual=sat_actual,
                   sat_rate=jnp.mean(sat_actual))
        return loss_g, aux

    def losses_d(d_params, batch, probs, sat_actual):
        probs = jax.lax.stop_gradient(probs)
        sat_logits = G.discriminator_apply(d_params, batch["net_enc"], probs,
                                           batch["obj_enc"],
                                           use_fused=cfg.use_fused, mesh=mesh)
        loss_dis = jnp.mean(G.satisfaction_ce(sat_logits, sat_actual))  # lines 12/15
        d_acc = jnp.mean(
            (jnp.argmax(sat_logits, -1).astype(jnp.float32) == sat_actual).astype(jnp.float32)
        )
        return loss_dis, dict(d_acc=d_acc)

    g_optim = adam(cfg.g_lr)
    d_optim = adam(cfg.d_lr)

    def step_body(carry, batch):
        g_params, d_params, g_opt, d_opt, rng = carry
        batch = constrain(batch)
        rng, nrng = jax.random.split(rng)
        noise = G.sample_noise(nrng, batch["net_enc"].shape[0], cfg)
        (loss_g, aux), g_grads = jax.value_and_grad(losses_g, has_aux=True)(
            g_params, d_params, batch, noise
        )
        g_upd, g_opt = g_optim.update(g_grads, g_opt)
        g_params = apply_updates(g_params, g_upd)

        (loss_d, daux), d_grads = jax.value_and_grad(losses_d, has_aux=True)(
            d_params, batch, aux["probs"], aux["sat_actual"]
        )
        d_upd, d_opt = d_optim.update(d_grads, d_opt)
        d_params = apply_updates(d_params, d_upd)

        metrics = dict(
            loss_g=loss_g, loss_d=loss_d,
            loss_config=aux["loss_config"], loss_critic=aux["loss_critic"],
            sat_rate=aux["sat_rate"], d_acc=daux["d_acc"],
        )
        return (g_params, d_params, g_opt, d_opt, rng), metrics

    return g_optim, d_optim, step_body


def make_train_step(model: DesignModel, cfg: G.GANConfig,
                    use_jax_oracle: Optional[bool] = None,
                    mesh=None):
    """Build the jitted per-batch update implementing Algorithm 1.

    Kept as the single-batch entry point (benchmarks, tests); the epoch
    loop in ``train_gan`` scans the same body via ``make_epoch_fn``.
    ``mesh``: see ``_make_step_body`` (data-parallel over its batch axes).
    """
    g_optim, d_optim, step_body = _make_step_body(model, cfg, use_jax_oracle,
                                                  mesh=mesh)

    @jax.jit
    def step(g_params, d_params, g_opt, d_opt, batch, rng):
        carry, metrics = step_body((g_params, d_params, g_opt, d_opt, rng), batch)
        g_params, d_params, g_opt, d_opt, rng = carry
        return g_params, d_params, g_opt, d_opt, rng, metrics

    return g_optim, d_optim, step


def make_epoch_fn(model: DesignModel, cfg: G.GANConfig,
                  use_jax_oracle: Optional[bool] = None,
                  mesh=None):
    """Whole-epoch update: one jitted scan over pre-gathered batches.

    epoch(carry, data, perm) -> (carry, metrics):
      carry = (g_params, d_params, g_opt, d_opt, rng), donated;
      data  = dict of full device-resident encoded dataset arrays (N, ...);
      perm  = (n_batches, batch_size) int32 row indices for this epoch.
    The batch gather happens on device, so per-epoch host work is one
    permutation draw and one dispatch.

    With a ``mesh``, hand in the carry replicated (``shard.replicate``),
    the data replicated, and the perm sharded on its batch-size axis
    (``shard.put_sharded(perm, axis=1)``): each device then gathers only
    its own rows and the scanned step runs data-parallel end to end with
    the donated carry staying replicated — what ``train_gan`` does.
    """
    g_optim, d_optim, step_body = _make_step_body(model, cfg, use_jax_oracle,
                                                  mesh=mesh)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def epoch(carry, data, perm):
        batches = jax.tree.map(lambda a: a[perm], data)
        return jax.lax.scan(step_body, carry, batches)

    return g_optim, d_optim, epoch


@functools.lru_cache(maxsize=16)
def _cached_epoch_fn(model: DesignModel, cfg: G.GANConfig,
                     use_jax_oracle: Optional[bool], mesh):
    """Memoized `make_epoch_fn`: repeated `train_gan` calls on the same
    (model, cfg, oracle route, mesh) — the online loop's incremental
    generations — reuse one jitted epoch instead of retracing per call.
    Keys by model identity (design models are stateless oracles) and by
    `GANConfig`/mesh value; with the training arrays' shapes held constant
    (`repro.serve.online.HardReplay` fixes the dataset size for exactly
    this reason) a warm generation is zero-recompile."""
    return make_epoch_fn(model, cfg, use_jax_oracle, mesh=mesh)


def encode_batch(model: DesignModel, ds: Dataset, idx: np.ndarray) -> Dict[str, np.ndarray]:
    net_idx = ds.net_idx[idx]
    return {
        "net_idx": net_idx.astype(np.int32),
        "net_enc": ds.net_encoded(model, net_idx),
        "cfg_onehot": model.space.onehot_from_indices(ds.cfg_idx[idx]),
        # sample objectives: the sample's own (L, P) are the objectives it
        # satisfies exactly (dataset rows double as (objective, witness)).
        "obj_enc": ds.obj_encoded(ds.latency[idx], ds.power[idx]),
        "lat_obj": ds.latency[idx].astype(np.float32),
        "pow_obj": ds.power[idx].astype(np.float32),
    }


def encode_dataset(model: DesignModel, ds: Dataset) -> Dict[str, jnp.ndarray]:
    """Encode every row once and upload to device (train_gan hot-path)."""
    full = encode_batch(model, ds, np.arange(ds.n))
    return {k: jnp.asarray(v) for k, v in full.items()}


def train_gan(
    model: DesignModel,
    ds: Dataset,
    cfg: G.GANConfig,
    iters: int = 5,
    seed: int = 0,
    log_every: int = 0,
    use_jax_oracle: Optional[bool] = None,
    mesh=None,
    state: Optional[TrainState] = None,
) -> TrainState:
    """Mini-batch alternating training (Algorithm 1, lines 1-21).

    Each iteration is one device-resident ``lax.scan`` over the epoch's
    batches; the dataset is encoded and uploaded exactly once.

    ``state`` warm-starts from an earlier `TrainState` (params, optimizer
    moments, and rng all resume; ``seed`` then only drives the epoch
    permutations): the incremental-training entry the online improvement
    loop (`repro.serve.online`) uses to fine-tune generation N from
    generation N-1 instead of re-initializing.  The jitted epoch is
    memoized on (model, cfg, oracle route, mesh), so warm incremental
    calls do not retrace.

    ``mesh=None`` picks up the active task mesh (``shard.set_task_mesh``);
    with one, each epoch runs data-parallel over the mesh's batch axes —
    replicated donated carry, per-device row gathers, gradients
    all-reduced over ('pod', 'data') — and falls back, with a
    RuntimeWarning, to the unsharded one-device path when the batch size
    does not divide the shard count.  Losses are
    batch means either way, so sharded training matches single-device up
    to float reduction order (pinned by tests/test_shard.py).
    """
    mesh = shard.get_task_mesh() if mesh is None else mesh
    k = shard.n_task_shards(mesh)
    if k <= 1:
        mesh = None
    elif min(cfg.batch_size, ds.n) % k:
        warnings.warn(f"train_gan: batch {min(cfg.batch_size, ds.n)} does "
                      f"not divide the {k} task shards; training unsharded "
                      f"on one device", RuntimeWarning, stacklevel=2)
        mesh = None
    g_optim, d_optim, epoch = _cached_epoch_fn(model, cfg, use_jax_oracle,
                                               mesh)
    if state is not None:
        g_params, d_params = state.g_params, state.d_params
        g_opt, d_opt, rng = state.g_opt, state.d_opt, state.rng
    else:
        rng = jax.random.PRNGKey(seed)
        rng, g_rng, d_rng = jax.random.split(rng, 3)
        g_params = G.init_generator(g_rng, cfg, model.space)
        d_params = G.init_discriminator(d_rng, cfg, model.space)
        g_opt = g_optim.init(g_params)
        d_opt = d_optim.init(d_params)

    np_rng = np.random.default_rng(seed)
    n = ds.n
    bs = min(cfg.batch_size, n)
    n_batches = n // bs
    data = encode_dataset(model, ds)
    if mesh is not None:
        data = shard.replicate(data, mesh)

    carry = (g_params, d_params, g_opt, d_opt, rng)
    if mesh is not None:
        carry = shard.replicate(carry, mesh)
    history: List[Dict[str, float]] = []
    t0 = time.time()
    for it in range(iters):
        perm = np_rng.permutation(n)[: n_batches * bs]
        perm = perm.reshape(n_batches, bs).astype(np.int32)
        perm = shard.put_sharded(perm, mesh, axis=1) if mesh is not None \
            else jnp.asarray(perm)
        with warnings.catch_warnings():
            # CPU backends can't honor buffer donation; that is fine here.
            warnings.filterwarnings("ignore", message="Some donated buffers")
            carry, metrics = epoch(carry, data, perm)
        metrics = {k: np.asarray(v) for k, v in metrics.items()}
        for b in range(n_batches):
            rec = {k: float(v[b]) for k, v in metrics.items()}
            rec["iter"] = it
            history.append(rec)
        if log_every and (it % log_every == 0):
            m = history[-1]
            print(f"[train_gan] iter={it} loss_g={m['loss_g']:.4f} "
                  f"loss_d={m['loss_d']:.4f} critic={m['loss_critic']:.4f} "
                  f"sat={m['sat_rate']:.3f} t={time.time()-t0:.1f}s")

    g_params, d_params, g_opt, d_opt, rng = carry
    return TrainState(g_params, d_params, g_opt, d_opt, rng, history)
