"""Design Explorer — GAN inference + candidate configuration sets (§6.1).

"For each configuration, if the one-hot output of one choice exceeds the
probability threshold, the choice is employed.  Then the candidate
configuration sets are the combinations of all the employed choices of all
the configurations."

The cartesian product can explode combinatorially; we cap it at
``max_candidates`` by trimming the lowest-probability employed choices
(argmax choices are never trimmed), which preserves the paper's behaviour
for realistic thresholds while bounding memory.

Two routes produce identical candidate sets:

- ``enumerate_candidates``: host numpy + ``itertools.product`` for one task;
- ``enumerate_candidates_batch``: the device-resident batch twin — threshold
  mask -> per-group employed counts -> mixed-radix index arithmetic that
  unravels the cartesian product directly into a ``(T, C_pad, n_dims)``
  padded candidate tensor, with ``C_pad`` bucketed to the next power of two
  so the jit cache stays bounded.

A third route consumes the same enumeration *without* the dense tensor:
``core/fused_select`` applies the identical mixed-radix arithmetic to
tile-sized index windows inside one fused enumerate->score->select
program, which is how caps beyond the dense materialization bound
(``_DENSE_LIM``) up to ``_PROD_LIM = 2**26`` are reached.  Both routes
share the traceable cores in ``_enum_core`` so they cannot drift.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gan as G
from repro.core import shard
from repro.core.encoding import ConfigSpace, padded_group_layout
from repro.dataset.generator import Dataset
from repro.design_models.base import DesignModel


@dataclasses.dataclass
class ExplorerConfig:
    prob_threshold: float = 0.2
    max_candidates: int = 4096
    noise_samples: int = 1     # forward passes with independent noise
    #: batched-route selection: "fused" streams candidate tiles through one
    #: enumerate->score->select program (caps up to _PROD_LIM = 2**26);
    #: "dense" keeps the reference route that materializes the (T, C_pad,
    #: n_dims) tensor (caps up to _DENSE_LIM = 2**20).  Selections are
    #: bit-identical either way (tests/test_fused_select.py).
    batch_route: str = "fused"
    #: fused tile width — peak candidate memory is O(T * select_tile * d)
    select_tile: int = 1024


# canonical definition lives beside the padding helpers it feeds;
# re-exported here for the historical import path (selector, batcher)
pow2_bucket = shard.pow2_bucket


def row_seeds(seed, n: int) -> np.ndarray:
    """THE per-row seed convention, shared by every engine route: a scalar
    ``seed`` -> seed + arange(n) (row t explores with seed + t); an (n,)
    array -> as-is (row t explores with seed[t] — how the serve
    micro-batcher keeps coalesced requests' results independent of batch
    placement).  Host int64 either way (see `task_keys`)."""
    if np.ndim(seed) == 0:
        return np.arange(n, dtype=np.int64) + int(seed)
    seeds = np.asarray(seed, np.int64).reshape(-1)
    assert seeds.shape[0] == n, (seeds.shape, n)
    return seeds


def task_seeds(seed, n: int) -> np.ndarray:
    """Per-task noise seeds: `row_seeds(seed, n)` masked to its low 32 bits
    in host int64, as uint32 — what PRNGKey turns into task t's key.

    The sum must not happen in device int32: Python-int seeds >= 2**31 raise
    OverflowError at dispatch, and in-range seeds whose sum crosses 2**31
    wrap mod 2**32 — aliasing task keys with those of other (wrapped) seeds.
    Masking the int64 sum to its low 32 bits before PRNGKey is bitwise
    identical to the legacy int32 route for every seed it accepted
    (including negatives), while keeping any int64 seed valid and collision
    -free within a batch.
    """
    return (row_seeds(seed, n) & np.int64(0xFFFFFFFF)).astype(np.uint32)


def task_keys(seed, n: int) -> jnp.ndarray:
    """Per-task noise keys: PRNGKey over `task_seeds(seed, n)`, eagerly.
    The explorer's forward derives the same keys inside its program."""
    return jax.vmap(jax.random.PRNGKey)(task_seeds(seed, n))


def _employed_choices(probs_g: np.ndarray, thresh: float) -> List[np.ndarray]:
    """Per group: indices of choices above threshold (argmax always kept)."""
    out = []
    for g in probs_g:
        keep = np.flatnonzero(g > thresh)
        if keep.size == 0:
            keep = np.array([int(np.argmax(g))])
        out.append(keep)
    return out


def _trimmed_employed(
    space: ConfigSpace,
    probs: np.ndarray,
    thresh: float,
    max_candidates: int,
) -> List[np.ndarray]:
    """Per-group employed choice sets after the candidate cap (host route)."""
    groups = [np.asarray(g) for g in space.split_groups(probs)]
    employed = _employed_choices(groups, thresh)

    counts = [len(e) for e in employed]
    product = 1
    for c in counts:
        product *= c
    if product > max_candidates:
        # cap the cartesian product: drop non-argmax employed choices in
        # ascending probability order until the product fits (one argsort —
        # dropping a choice never changes the other probabilities, so the
        # ascending order IS the greedy drop-the-global-minimum order; the
        # stable sort resolves ties in group-major, choice-major order, the
        # order a greedy re-scan would visit them).  Argmax choices are
        # never droppable, so the product always reaches <= max_candidates
        # (worst case: every group collapses to its argmax, product 1).
        gis, cis, ps = [], [], []
        for gi, (g, e) in enumerate(zip(groups, employed)):
            am = int(np.argmax(g))
            for ci in e:
                if ci != am:
                    gis.append(gi)
                    cis.append(int(ci))
                    ps.append(g[ci])
        dropped = [set() for _ in groups]
        for k in np.argsort(np.asarray(ps), kind="stable"):
            if product <= max_candidates:
                break
            gi = gis[k]
            dropped[gi].add(cis[k])
            product = product // counts[gi] * (counts[gi] - 1)
            counts[gi] -= 1
        employed = [
            e[~np.isin(e, sorted(d))] if d else e
            for e, d in zip(employed, dropped)
        ]
    return employed


def enumerate_candidates(
    space: ConfigSpace,
    probs: np.ndarray,
    thresh: float,
    max_candidates: int,
) -> np.ndarray:
    """probs: (onehot_width,) -> (C, n_dims) int candidate index matrix."""
    employed = _trimmed_employed(space, probs, thresh, max_candidates)
    return np.array(list(itertools.product(*employed)), dtype=np.int32)


# ---------------------------------------------------------------------------
# device-resident batched enumeration
# ---------------------------------------------------------------------------
#: largest max_candidates any batched route accepts (asserted at entry).
#: Running cartesian-product values are clamped to _PROD_CLAMP during the
#: on-device trim: strictly above any permitted cap, so a clamped value
#: still compares `> cap` correctly.  The divide-form overflow guard in
#: ``_clamped_product`` keeps every partial product exact int32 at this
#: cap (the old multiply-then-min form needed clamp * 1024 < 2**31 and
#: topped out at 2**20).
_PROD_LIM = 1 << 26
_PROD_CLAMP = _PROD_LIM + 1
#: largest cap the *dense* route will materialize as a (T, C_pad, n_dims)
#: tensor; beyond it, only the streaming tiled route (core/fused_select)
#: applies — it never materializes more than a tile.
_DENSE_LIM = 1 << 20


@functools.lru_cache(maxsize=None)
def _enum_core(space: ConfigSpace):
    """Traceable enumeration cores shared by the dense jitted wrappers
    (``_batched_enum_fns``) and the streaming tiled route
    (``core/fused_select``).

    ``masks_core``: probs (T, onehot_width) -> per-group keep masks +
    counts + totals, applying the same threshold/argmax/trim rules as the
    host ``enumerate_candidates`` (bit-for-bit: same probs in -> same sets
    out).  ``radix_core``: the kept sets -> the mixed-radix (table, stride)
    pair whose digit arithmetic unravels the cartesian product in
    ``itertools.product`` order.  One definition feeds both consumers, so
    the routes cannot drift.
    """
    gidx, mask, _ = padded_group_layout(space)
    n_groups, mx = mask.shape
    mask_j = jnp.asarray(mask)

    def _clamped_product(counts):
        # python loop over the (static, small) group count.  The guard is
        # divide-form so the product is only computed when it stays below
        # the clamp (exact for positive ints: p*c > clamp <=> p > clamp//c)
        # — no partial product ever exceeds _PROD_CLAMP < 2**31, at any
        # permitted cap.  The wrapped multiply in the rejected lane of the
        # `where` is discarded, never selected.
        p = jnp.int32(1)
        for g in range(n_groups):
            c = counts[g]
            over = p > _PROD_CLAMP // c
            p = jnp.where(over, jnp.int32(_PROD_CLAMP), p * c)
        return p

    def _masks_one(probs_pad, thresh, cap):
        am = jnp.argmax(probs_pad, axis=-1)
        am_oh = jnp.arange(mx)[None, :] == am[:, None]
        emp = (mask_j & (probs_pad > thresh)) | am_oh    # argmax always kept
        droppable = (emp & ~am_oh).reshape(-1)
        p_flat = jnp.where(droppable, probs_pad.reshape(-1), jnp.inf)
        order = jnp.argsort(p_flat)          # stable: host-loop tie order
        counts0 = emp.sum(axis=-1).astype(jnp.int32)

        def step(counts, slot):
            do = droppable[slot] & (_clamped_product(counts) > cap)
            counts = counts.at[slot // mx].add(-do.astype(jnp.int32))
            return counts, do

        counts, dropped = jax.lax.scan(step, counts0, order)
        keep = emp & ~jnp.zeros_like(droppable).at[order].set(dropped) \
            .reshape(n_groups, mx)
        return keep, counts

    def masks_core(probs, thresh, cap):
        padded, _ = space.split_groups_padded(probs, fill=-jnp.inf)
        keep, counts = jax.vmap(_masks_one, in_axes=(0, None, None))(
            padded, thresh, cap)
        total = jnp.prod(counts, axis=-1)    # <= cap after trim: int32-safe
        return keep, counts, total

    def radix_core(keep, counts):
        table = jnp.argsort(~keep, axis=-1)  # kept slots first, ascending
        # row-major strides (last group fastest — itertools.product order)
        rev = jnp.cumprod(counts[:, ::-1], axis=-1)[:, ::-1]
        stride = jnp.concatenate([rev[:, 1:], jnp.ones_like(rev[:, :1])],
                                 axis=-1)
        return table, stride

    return masks_core, radix_core


@functools.lru_cache(maxsize=None)
def _batched_enum_fns(space: ConfigSpace):
    """Jitted (masks, unravel) pair for the dense on-device enumeration.

    Thin jit wrappers over ``_enum_core``: ``unravel`` applies the mixed
    -radix digit arithmetic to the full [0, c_pad) index range, yielding
    the (T, c_pad, n_dims) padded candidate tensor — ``c_pad`` is static
    so the jit cache holds one entry per power-of-two bucket.
    """
    masks_core, radix_core = _enum_core(space)
    masks = jax.jit(masks_core)

    @functools.partial(jax.jit, static_argnames="c_pad")
    def unravel(keep, counts, total, c_pad):
        table, stride = radix_core(keep, counts)
        j = jnp.arange(c_pad, dtype=jnp.int32)
        digit = (j[None, :, None] // stride[:, None, :]) % counts[:, None, :]
        cand = jnp.take_along_axis(table, digit.transpose(0, 2, 1), axis=-1)
        valid = j[None, :] < total[:, None]
        return cand.transpose(0, 2, 1).astype(jnp.int32), valid

    return masks, unravel


def enumerate_candidates_batch(
    space: ConfigSpace,
    probs,
    thresh: float,
    max_candidates: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, np.ndarray]:
    """Device twin of ``enumerate_candidates`` over a task batch.

    probs: (T, onehot_width) array (host or device) ->
      cand  (T, C_pad, n_dims) int32 device candidate indices,
      valid (T, C_pad) bool device mask of real (non-padding) rows,
      counts (T,) host int per-task candidate counts.

    Row t's first counts[t] candidates equal ``enumerate_candidates`` on
    probs[t] exactly.  C_pad is the next power of two >= max(counts),
    bucketing recompiles to at most log2(max_candidates) cache entries.

    This is the *reference* route: picking C_pad costs a mid-dispatch host
    sync (the ``np.asarray(total)`` below — the GL112 bug class) and the
    tensor caps out at ``_DENSE_LIM``.  The production batched path
    (``core/fused_select``) streams the same enumeration in tiles with
    neither limit.
    """
    assert space.max_group_size <= 1024 and 1 <= max_candidates <= _DENSE_LIM, \
        "dense route needs max group size <= 1024 and cap <= 2**20 " \
        "(use the fused tiled route for larger caps)"
    masks, unravel = _batched_enum_fns(space)
    keep, counts, total = masks(shard.put_sharded(probs), np.float32(thresh),
                                np.int32(max_candidates))
    counts_host = np.asarray(total)
    c_pad = pow2_bucket(int(counts_host.max(initial=1)))
    cand, valid = unravel(keep, counts, total, c_pad)
    return cand, valid, counts_host


def flatten_task_draws(net_enc, obj_enc, keys, n_samples: int, noise_fn):
    """THE (task, sample) -> row-batch layout of the chained (megakernel)
    inference route, shared by the explorer and the LargeMLP baseline so
    the per-task noise-stream parity contract lives in one place.

    noise_fn(key, s) -> (noise_dim,) draws sample s of a task's stream
    (the same fold_in(key, s) streams the vmap route uses).  Returns
    (net_rows, obj_rows, noise_rows), each (T * n_samples, ·), task-major
    — averaging back is ``rows.reshape(T, n_samples, -1).mean(axis=1)``.
    """
    t = net_enc.shape[0]
    noise = jax.vmap(lambda key: jax.vmap(
        lambda s: noise_fn(key, s))(jnp.arange(n_samples)))(keys)
    rep = lambda a: jnp.repeat(a[:, None], n_samples, axis=1) \
        .reshape(t * n_samples, -1)
    return rep(net_enc), rep(obj_enc), noise.reshape(t * n_samples, -1)


def _task_probs(space: ConfigSpace, gan_cfg: G.GANConfig, chained: bool,
                mesh, g_params, net_enc, obj_enc, keys, n_samples: int):
    """Traceable body of the explorer's G forward: (T, onehot_width) mean
    probs of each task's n_samples draws, from its (T,) noise keys."""
    if chained:
        def noise_fn(key, s):
            return G.sample_noise(jax.random.fold_in(key, s), 1, gan_cfg)[0]

        t = net_enc.shape[0]
        net_r, obj_r, noise_r = flatten_task_draws(
            net_enc, obj_enc, keys, n_samples, noise_fn)
        probs = G.generator_apply(
            g_params, space, net_r, obj_r, noise_r,
            use_fused=gan_cfg.use_fused, chained=True, mesh=mesh)
        return jnp.mean(probs.reshape(t, n_samples, -1), axis=1)

    def one_task(net, obj, key):
        def one(s):
            noise = G.sample_noise(jax.random.fold_in(key, s), 1, gan_cfg)
            return G.generator_apply(g_params, space, net[None], obj[None],
                                     noise, use_fused=gan_cfg.use_fused,
                                     mesh=mesh)[0]
        return jnp.mean(jax.vmap(one)(jnp.arange(n_samples)), axis=0)

    return jax.vmap(one_task)(net_enc, obj_enc, keys)


@functools.lru_cache(maxsize=None)
def _cached_fwd(space: ConfigSpace, gan_cfg: G.GANConfig,
                chained: bool = None, mesh=None):
    """Module-level jitted G inference, cached on (space, gan_cfg, mesh): a
    fresh Explorer (e.g. per retrain / hot-swap) reuses the compiled
    forward instead of recompiling from scratch.  ``mesh`` is the task
    mesh the forward's inputs are sharded over (None = one device); the
    kernel route runs per shard on it (kernels/dispatch.py).

    The forward takes the (T,) uint32 `task_seeds` and derives each task's
    PRNGKey inside the program (bitwise the keys `task_keys` builds), so a
    warm call runs no eager JAX on the host.  Per-task noise streams: task
    t averages n_samples draws from fold_in(key[t], s) — the same streams
    whether tasks run one at a time or batched, which is the
    batched-vs-sequential parity contract.

    ``chained`` (None = dispatch auto, i.e. TPU) flattens the (T, samples)
    draws into one row batch and runs G through the layer-chained Pallas
    megakernel — one big dispatch instead of a vmap of width-1 forwards.
    Same noise streams either way; off the fused path the vmap structure
    (and its numerics) is unchanged.
    """
    from repro.kernels import dispatch as D
    if chained is None:
        chained = D.fused_enabled(gan_cfg.use_fused) and D.on_tpu()

    @functools.partial(jax.jit, static_argnames="n_samples")
    def fwd(g_params, net_enc, obj_enc, seeds, n_samples):
        keys = jax.vmap(jax.random.PRNGKey)(seeds)
        return _task_probs(space, gan_cfg, chained, mesh, g_params, net_enc,
                           obj_enc, keys, n_samples)

    return fwd


@dataclasses.dataclass
class Explorer:
    """Trained-G wrapper: task -> candidate configuration sets."""

    model: DesignModel
    ds: Dataset                 # carries the normalizers
    g_params: dict
    gan_cfg: G.GANConfig
    cfg: ExplorerConfig = dataclasses.field(default_factory=ExplorerConfig)

    @property
    def _fwd(self):
        """The jitted G forward for the active task mesh (cached)."""
        return _cached_fwd(self.model.space, self.gan_cfg,
                           mesh=shard.get_task_mesh())

    def generator_probs_device(self, net_idx: np.ndarray, lat_obj, pow_obj,
                               seed: int = 0) -> jnp.ndarray:
        """Vmapped G forward: (T, onehot_width) device mean probs.

        Task row t draws its noise from PRNGKey(seed + t) — or PRNGKey
        (seed[t]) when ``seed`` is a per-task array — so row t is
        bitwise-equal to a single-task call with that seed: batching a task
        never changes its candidates.  The sum runs in host int64 (see
        `task_seeds`) so large seeds neither raise nor alias.

        The inputs stay numpy: the jitted forward transfers them itself
        and derives the keys on device.  When a task mesh is active
        (``shard.set_task_mesh``) and the task count divides its shard
        count, the inputs land task-sharded over the mesh and the same
        jitted forward runs SPMD across devices — lane numerics (and thus
        candidates) are unchanged.
        """
        net_enc = self.ds.net_encoded(self.model, np.atleast_2d(net_idx))
        obj_enc = self.ds.obj_encoded(np.atleast_1d(lat_obj),
                                      np.atleast_1d(pow_obj))
        seeds = task_seeds(seed, net_enc.shape[0])
        return self._fwd(self.g_params, shard.put_sharded(net_enc),
                         shard.put_sharded(obj_enc), shard.put_sharded(seeds),
                         n_samples=self.cfg.noise_samples)

    def generator_probs(self, net_idx: np.ndarray, lat_obj, pow_obj,
                        seed: int = 0) -> np.ndarray:
        """Host-array view of `generator_probs_device`."""
        return np.asarray(
            self.generator_probs_device(net_idx, lat_obj, pow_obj, seed))

    def candidates(self, net_idx: np.ndarray, lat_obj: float, pow_obj: float,
                   seed: int = 0) -> np.ndarray:
        probs = self.generator_probs(net_idx, lat_obj, pow_obj, seed)[0]
        return enumerate_candidates(
            self.model.space, probs, self.cfg.prob_threshold, self.cfg.max_candidates
        )

    def candidates_batch(self, net_idx: np.ndarray, lat_obj, pow_obj,
                         seed: int = 0):
        """Device-resident candidates for a task batch: G inference and the
        cartesian-product enumeration both stay on device; see
        `enumerate_candidates_batch` for the return contract."""
        probs = self.generator_probs_device(net_idx, lat_obj, pow_obj, seed)
        return enumerate_candidates_batch(
            self.model.space, probs, self.cfg.prob_threshold,
            self.cfg.max_candidates
        )
