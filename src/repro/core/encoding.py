"""Feature encoding for GAN-based DSE (paper §6.1).

Configurations are one-hot encoded: "most of the configurations of the
architectures and mapping strategies are not successive and only some
specific numbers are meaningful".  The user's objectives and the network
parameters are encoded as (binary) numbers normalized by the standard
deviation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import numpy as np
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ConfigDim:
    """One configuration dimension with its discrete legal choices."""

    name: str
    choices: Tuple[float, ...]

    @property
    def n(self) -> int:
        return len(self.choices)


@dataclasses.dataclass(frozen=True)
class ConfigSpace:
    """The discrete design space: a product of one-hot `ConfigDim`s."""

    dims: Tuple[ConfigDim, ...]

    @property
    def n_dims(self) -> int:
        return len(self.dims)

    @property
    def onehot_width(self) -> int:
        return sum(d.n for d in self.dims)

    @functools.cached_property
    def group_sizes(self) -> Tuple[int, ...]:
        # cached: admission range-checks every served request against it
        return tuple(d.n for d in self.dims)

    @property
    def size(self) -> int:
        out = 1
        for d in self.dims:
            out *= d.n
        return out

    @property
    def max_group_size(self) -> int:
        return max(d.n for d in self.dims)

    # ---- index <-> value -------------------------------------------------
    def values_from_indices(self, idx: np.ndarray) -> np.ndarray:
        """idx: (..., n_dims) integer choice indices -> (..., n_dims) values."""
        idx = np.asarray(idx)
        cols = [np.asarray(d.choices)[idx[..., i]] for i, d in enumerate(self.dims)]
        return np.stack(cols, axis=-1)

    def indices_from_values(self, vals: np.ndarray) -> np.ndarray:
        vals = np.asarray(vals)
        cols = []
        for i, d in enumerate(self.dims):
            table = np.asarray(d.choices)
            # nearest legal choice (values are expected to be exact members)
            cols.append(np.argmin(np.abs(vals[..., i, None] - table[None, :]), axis=-1))
        return np.stack(cols, axis=-1)

    # ---- one-hot ---------------------------------------------------------
    def onehot_from_indices(self, idx: np.ndarray) -> np.ndarray:
        """(..., n_dims) -> (..., onehot_width) float32 one-hot."""
        idx = np.asarray(idx)
        parts = []
        for i, d in enumerate(self.dims):
            parts.append(np.eye(d.n, dtype=np.float32)[idx[..., i]])
        return np.concatenate(parts, axis=-1)

    def indices_from_onehot(self, oh: np.ndarray) -> np.ndarray:
        """(..., onehot_width) (soft ok) -> argmax per group -> (..., n_dims)."""
        oh = np.asarray(oh)
        out, off = [], 0
        for d in self.dims:
            out.append(np.argmax(oh[..., off : off + d.n], axis=-1))
            off += d.n
        return np.stack(out, axis=-1)

    def split_groups(self, flat):
        """Split a (..., onehot_width) array into per-dim groups (jnp-safe)."""
        out, off = [], 0
        for d in self.dims:
            out.append(flat[..., off : off + d.n])
            off += d.n
        return out

    def sample_indices(self, rng: np.random.Generator, n: int) -> np.ndarray:
        """Evenly sample the design space (paper §5.1 dataset generator)."""
        return np.stack(
            [rng.integers(0, d.n, size=n) for d in self.dims], axis=-1
        )

    def split_groups_padded(self, flat, fill=0.0) -> Tuple[jnp.ndarray, np.ndarray]:
        """Batched padded per-group view: (..., onehot_width) -> (..., n_dims,
        max_group_size) with `fill` in the padding slots, plus the (n_dims,
        max_group_size) validity mask.  One wide gather instead of a ragged
        slice chain — the jnp twin of `split_groups` for vectorized per-group
        ops (softmax, threshold masks, argmax) over arbitrary leading dims.
        """
        gidx, mask, _ = padded_group_layout(self)
        return jnp.where(mask, flat[..., gidx], fill), mask

    def values_from_indices_jax(self, idx) -> jnp.ndarray:
        """jnp twin of `values_from_indices`: traceable constant-table gather.

        idx: (..., n_dims) integer choice indices -> (..., n_dims) float32
        values.  The choice tables are baked into the jaxpr as constants so
        design-model oracles built on this stay device-resident.
        """
        cols = [
            jnp.take(jnp.asarray(d.choices, jnp.float32), idx[..., i], axis=0)
            for i, d in enumerate(self.dims)
        ]
        return jnp.stack(cols, axis=-1)


@functools.lru_cache(maxsize=None)
def padded_group_layout(space: ConfigSpace):
    """Constant index maps for vectorized per-group ops.

    Groups have ragged sizes; padding them to (n_dims, max_n) lets per-group
    softmax/threshold/argmax run as ONE wide op instead of a slice/concat
    chain per group (which costs a long tail of small kernels per step).
    Returns (gather_idx (n_dims, max_n), mask, flat_scatter (onehot_width,)):
    ``flat[..., gather_idx]`` -> padded view; ``padded.reshape(..., -1)
    [..., flat_scatter]`` -> flat view.  Plain numpy outputs: they embed as
    jaxpr constants (device arrays here would leak tracers through the
    cache when first built under a trace).
    """
    sizes = space.group_sizes
    mx = max(sizes)
    gidx = np.zeros((len(sizes), mx), np.int32)
    mask = np.zeros((len(sizes), mx), bool)
    flat2pad = np.zeros(space.onehot_width, np.int32)
    off = 0
    for g, n in enumerate(sizes):
        for j in range(n):
            gidx[g, j] = off + j
            mask[g, j] = True
            flat2pad[off + j] = g * mx + j
        off += n
    return gidx, mask, flat2pad


@dataclasses.dataclass(frozen=True)
class Normalizer:
    """Standard-deviation normalization for objectives / net params (§6.1)."""

    mean: np.ndarray
    std: np.ndarray

    @staticmethod
    def fit(x: np.ndarray, center: bool = False) -> "Normalizer":
        x = np.asarray(x, np.float64)
        std = x.std(axis=0)
        std = np.where(std < 1e-12, 1.0, std)
        mean = x.mean(axis=0) if center else np.zeros(x.shape[-1])
        return Normalizer(mean=mean, std=std)

    def __call__(self, x):
        return (x - self.mean) / self.std

    def inverse(self, x):
        return x * self.std + self.mean

    def to_dict(self) -> Dict[str, List[float]]:
        return {"mean": self.mean.tolist(), "std": self.std.tolist()}

    @staticmethod
    def from_dict(d) -> "Normalizer":
        return Normalizer(np.asarray(d["mean"]), np.asarray(d["std"]))


def binary_log2_encode(vals: np.ndarray) -> np.ndarray:
    """Encode positive integer-ish parameters on a log2 scale.

    The paper encodes network parameters 'as the binary numbers'; since all
    net params / choices in Tables 1-3 are powers-of-two-ish magnitudes, a
    log2 magnitude encoding carries the same information in a compact,
    scale-free way and is what we feed the MLPs (then std-normalized).
    """
    return np.log2(np.maximum(np.asarray(vals, np.float64), 1e-9))
