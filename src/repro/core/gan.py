"""The conditional GAN of GANDSE (paper §4, §6.1, Table 4).

Generator  G(net_params, objectives, noise) -> per-config-group one-hot
           probability distributions (softmax per group).
Discriminator D(net_params, config_onehot, objectives) -> satisfaction
           logits (2-class one-hot, like other classification tasks).

Both are multilayer perceptrons with ReLU activations and Adam optimizers
(Table 4).  Params are pure pytrees; everything jit-able.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encoding import ConfigSpace, padded_group_layout
from repro.nn import layers as L

#: shared with encoding.py — the padded per-group layout is also the basis of
#: the explorer's on-device candidate enumeration
_padded_layout = padded_group_layout


@dataclasses.dataclass(frozen=True)
class GANConfig:
    """Hyperparameters (paper Table 4; reduced defaults for CPU CI)."""

    n_net: int                    # encoded network-parameter width
    n_obj: int = 2                # latency + power objectives
    noise_dim: int = 8            # "small random numbers as noise"
    g_hidden_layers: int = 11
    g_neurons: int = 2048
    d_hidden_layers: int = 11
    d_neurons: int = 2048
    g_lr: float = 2e-5
    d_lr: float = 2e-5
    w_critic: float = 0.5
    batch_size: int = 1024
    dtype: str = "float32"
    #: Pallas fused-MLP fast path: None = backend auto (TPU on, CPU/GPU
    #: off), True/False force it (kernels/dispatch.py is the one rule).
    #: Threads through training (per-layer fused_dense with its
    #: custom_vjp) and inference (layer-chained megakernel).
    use_fused: Optional[bool] = None

    def scaled(self, layers: int, neurons: int, lr: float | None = None,
               batch_size: int | None = None) -> "GANConfig":
        """Reduced-scale variant (CPU CI); same algorithm."""
        return dataclasses.replace(
            self,
            g_hidden_layers=layers, d_hidden_layers=layers,
            g_neurons=neurons, d_neurons=neurons,
            g_lr=lr or self.g_lr, d_lr=lr or self.d_lr,
            batch_size=batch_size or self.batch_size,
        )


def init_generator(rng, cfg: GANConfig, space: ConfigSpace):
    in_dim = cfg.n_net + cfg.n_obj + cfg.noise_dim
    hidden = [cfg.g_neurons] * cfg.g_hidden_layers
    return L.mlp_init(rng, in_dim, hidden, space.onehot_width)


def init_discriminator(rng, cfg: GANConfig, space: ConfigSpace):
    in_dim = cfg.n_net + space.onehot_width + cfg.n_obj
    hidden = [cfg.d_neurons] * cfg.d_hidden_layers
    return L.mlp_init(rng, in_dim, hidden, 2)


def generator_apply(params, space: ConfigSpace, net_enc, obj_enc, noise,
                    use_fused: Optional[bool] = None, chained: bool = False,
                    mesh=None):
    """Returns (B, onehot_width) per-group softmax probabilities.

    ``use_fused`` follows the dispatch rule (None = backend auto);
    ``chained=True`` takes the layer-chained megakernel on the fused
    route — the inference fast path (training wants the per-layer
    backward, so the train step leaves it False).  ``mesh``: the devices
    the calling program spans (kernels/dispatch.py).
    """
    x = jnp.concatenate([net_enc, obj_enc, noise], axis=-1)
    if chained:
        logits = L.mlp_apply_chained(params, x, use_fused=use_fused,
                                     mesh=mesh)
    else:
        logits = L.mlp_apply(params, x, use_fused=use_fused, mesh=mesh)
    gidx, mask, flat2pad = _padded_layout(space)
    padded = jnp.where(mask, logits[..., gidx], -jnp.inf)
    probs = jax.nn.softmax(padded, axis=-1)      # pad -inf -> exactly 0
    return probs.reshape(*probs.shape[:-2], -1)[..., flat2pad]


def discriminator_apply(params, net_enc, cfg_onehot, obj_enc,
                        use_fused: Optional[bool] = None, mesh=None):
    """Returns (B, 2) satisfaction logits ([False, True] classes)."""
    x = jnp.concatenate([net_enc, cfg_onehot, obj_enc], axis=-1)
    return L.mlp_apply(params, x, use_fused=use_fused, mesh=mesh)


def replicate_params(params, mesh=None):
    """Pin a params pytree replicated across the task mesh — the pure-DP
    layout whose gradients GSPMD all-reduces over the batch axes.  No-op
    when no mesh is active, so single-device callers are untouched."""
    from repro.core import shard
    return shard.replicate(params, mesh)


def sample_noise_dim(rng, batch: int, noise_dim: int):
    """The canonical noise input ("small random numbers"): shared by G and
    the Large-MLP baseline, which §7.1.4 feeds the same noise as G."""
    return jax.random.uniform(rng, (batch, noise_dim), jnp.float32, -0.1, 0.1)


def sample_noise(rng, batch: int, cfg: GANConfig):
    return sample_noise_dim(rng, batch, cfg.noise_dim)


# ---------------------------------------------------------------------------
# losses (all cross-entropy, §6.1)
# ---------------------------------------------------------------------------
def grouped_cross_entropy(space: ConfigSpace, target_onehot, probs) -> jnp.ndarray:
    """E(Config_s, Config_g): summed per-group CE between the dataset
    config (one-hot) and G's per-group distributions.  (B,)

    Because the target is one-hot within each group, the sum of per-group
    CEs equals a single sum over the whole one-hot width — one wide op
    instead of a per-group slice/log/reduce chain (cheaper fwd and bwd).
    """
    eps = 1e-9
    return -jnp.sum(target_onehot * jnp.log(probs + eps), axis=-1)


# a training loss over dataset labels, not a feasibility judge: the oracle
# guarantees finite metrics before they reach here.
# lint: disable=nan-transparent-violation
def satisfaction_ce(logits, sat_true: jnp.ndarray) -> jnp.ndarray:
    """E(Sat, label): 2-class CE; sat_true is bool/float (B,). (B,)"""
    labels = jnp.stack([1.0 - sat_true, sat_true], axis=-1)  # [False, True]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(labels * logp, axis=-1)


def decode_hard(space: ConfigSpace, probs):
    """Per-group argmax -> (B, n_dims) int32 choice indices (jnp)."""
    gidx, mask, _ = _padded_layout(space)
    padded = jnp.where(mask, probs[..., gidx], -jnp.inf)
    return jnp.argmax(padded, axis=-1).astype(jnp.int32)


def indices_to_values(space: ConfigSpace, idx):
    """jnp version of ConfigSpace.values_from_indices (constant tables)."""
    cols = []
    for i, d in enumerate(space.dims):
        table = jnp.asarray(d.choices, jnp.float32)
        cols.append(jnp.take(table, idx[..., i]))
    return jnp.stack(cols, axis=-1)
