"""Plain reference for the GANDSE cells, written from the paper and the
configuration files alone.  It imports nothing of the program under test.

- the design spaces, read from the configuration file, and the float64
  oracle of its design model, ``oracles/<design_model>.py`` (a design
  model is added by adding its file; see ``oracles/__init__.py``);
- the encoding of network parameters and objectives (log2, then
  standardised by the mean and std of a data set);
- the conditional generator G as a plain float32 MLP with a softmax per
  configuration group;
- candidate enumeration (threshold, argmax always kept, the lowest
  probabilities trimmed until the product fits the cap) through
  ``itertools.product``;
- Algorithm 2, the sequential selector of the paper;
- Algorithm 1, one training step of G and D with Adam, for the training
  cell.

The control (a ``control`` argument) is the same code one precision
lower: for serving, each matrix product's inputs rounded to float8
(e4m3), the selector's metrics to bfloat16 and the winners' metrics in
float32; for training, parameters, Adam's moments and each product's
inputs in bfloat16.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from chipbench import harness

NOISE_TOL = 0.01          # paper section 7.2: 1% noise allowed when judging


# ---------------------------------------------------------------------------
# spaces
# ---------------------------------------------------------------------------
class Space:
    """A product of discrete dimensions, in the order of the config file."""

    def __init__(self, grid: Dict[str, Sequence[float]]):
        self.names = list(grid)
        self.choices = [np.asarray(v, np.float64) for v in grid.values()]
        self.sizes = [len(c) for c in self.choices]
        self.n_dims = len(self.sizes)
        self.onehot_width = int(sum(self.sizes))
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)])[:-1]

    def values(self, idx: np.ndarray) -> np.ndarray:
        idx = np.asarray(idx)
        return np.stack([c[idx[..., i]] for i, c in enumerate(self.choices)],
                        axis=-1)

    def onehot(self, idx: np.ndarray) -> np.ndarray:
        idx = np.asarray(idx)
        return np.concatenate([np.eye(n, dtype=np.float32)[idx[..., i]]
                               for i, n in enumerate(self.sizes)], axis=-1)

    def sample(self, rng: np.random.Generator, n: int) -> np.ndarray:
        return np.stack([rng.integers(0, k, size=n) for k in self.sizes],
                        axis=-1)

    def groups(self, flat: np.ndarray) -> List[np.ndarray]:
        return [flat[..., o:o + n] for o, n in zip(self.offsets, self.sizes)]


# ---------------------------------------------------------------------------
# the design model's oracle, float64 on the host
# ---------------------------------------------------------------------------
class Oracle:
    """(net indices, config indices) -> (latency, power), float64, by the
    configuration's design model in ``oracles/<design_model>.py``."""

    def __init__(self, cfg: dict):
        self.k = cfg["oracle_constants"]
        self.net_space = Space(cfg["net_space"])
        self.space = Space(cfg["config_space"])
        self.impl = harness.oracle(cfg["design_model"])

    def __call__(self, net_idx, cfg_idx):
        net = self.net_space.values(net_idx)
        c = self.space.values(cfg_idx)
        return self.impl.evaluate(self.k, net, c)


# ---------------------------------------------------------------------------
# data: training rows and DSE tasks (paper section 5.1, 7.1.2)
# ---------------------------------------------------------------------------
def sample_rows(oracle: Oracle, n: int, seed: int):
    """n feasible rows (net, config, latency, power), sampled evenly."""
    rng = np.random.default_rng(seed)
    nets, cfgs, lats, pows, got = [], [], [], [], 0
    while got < n:
        m = max(3 * n, 1024)
        net = oracle.net_space.sample(rng, m)
        c = oracle.space.sample(rng, m)
        lat, pw = oracle(net, c)
        ok = np.isfinite(lat) & np.isfinite(pw)
        nets.append(net[ok]); cfgs.append(c[ok])
        lats.append(lat[ok]); pows.append(pw[ok])
        got += int(ok.sum())
    cat = lambda parts: np.concatenate(parts)[:n]
    return cat(nets), cat(cfgs), cat(lats), cat(pows)


def sample_tasks(oracle: Oracle, n: int, seed: int, slack=(1.0, 2.5)):
    """n achievable tasks: a network and a witness config, whose metrics
    relaxed by a uniform slack factor are the objectives."""
    rng = np.random.default_rng(seed)
    nets, los, pos, got = [], [], [], 0
    while got < n:
        m = max(2 * n, 512)
        net = oracle.net_space.sample(rng, m)
        c = oracle.space.sample(rng, m)
        lat, pw = oracle(net, c)
        ok = np.isfinite(lat) & np.isfinite(pw)
        s_l = rng.uniform(slack[0], slack[1], size=m)
        s_p = rng.uniform(slack[0], slack[1], size=m)
        nets.append(net[ok]); los.append((lat * s_l)[ok])
        pos.append((pw * s_p)[ok])
        got += int(ok.sum())
    cat = lambda parts: np.concatenate(parts)[:n]
    return cat(nets), cat(los), cat(pos)


def log2_encode(vals):
    return np.log2(np.maximum(np.asarray(vals, np.float64), 1e-9))


class Encoder:
    """Network parameters and objectives as log2 values standardised by
    the mean and std of the data set's rows."""

    def __init__(self, net_space: Space, net_idx, lat, pw):
        fit = lambda x: (x.mean(axis=0),
                         np.where(x.std(axis=0) < 1e-12, 1.0, x.std(axis=0)))
        self.net_space = net_space
        self.net = fit(log2_encode(net_space.values(net_idx)))
        self.lat = fit(log2_encode(np.asarray(lat)[:, None]))
        self.pow = fit(log2_encode(np.asarray(pw)[:, None]))

    @staticmethod
    def _apply(ms, x):
        return (x - ms[0]) / ms[1]

    def net_enc(self, net_idx):
        return self._apply(self.net, log2_encode(
            self.net_space.values(net_idx))).astype(np.float32)

    def obj_enc(self, lat, pw):
        lo = self._apply(self.lat, log2_encode(np.asarray(lat)[..., None]))
        po = self._apply(self.pow, log2_encode(np.asarray(pw)[..., None]))
        return np.concatenate([lo, po], axis=-1).astype(np.float32)


# ---------------------------------------------------------------------------
# the GAN
# ---------------------------------------------------------------------------
def mlp_shapes(in_dim: int, hidden: int, layers: int, out_dim: int):
    dims = [in_dim] + [hidden] * layers + [out_dim]
    return list(zip(dims[:-1], dims[1:]))


def make_init(shapes):
    """One jitted call: key -> {"layers": [{"w", "b"}, ...]} in float32,
    He-normal hidden layers, 1/sqrt(fan_in) head, zero biases."""
    def init(key):
        keys = jax.random.split(key, len(shapes))
        out = []
        for i, ((k, n), kk) in enumerate(zip(shapes, keys)):
            s = (1.0 / k) ** 0.5 if i == len(shapes) - 1 else (2.0 / k) ** 0.5
            out.append({"w": jax.random.normal(kk, (k, n), jnp.float32) * s,
                        "b": jnp.zeros((n,), jnp.float32)})
        return {"layers": out}
    return jax.jit(init)


def _round(a, dtype):
    return a.astype(dtype).astype(jnp.float32)


def mlp(params, x, control=False):
    """Hidden ReLU layers, linear head; float32 products at "highest".
    ``control`` rounds each product's inputs first: True to float8 e4m3,
    "bf16" to bfloat16."""
    dtype = {True: jnp.float8_e4m3fn, "bf16": jnp.bfloat16}.get(control)
    layers = params["layers"]
    for i, p in enumerate(layers):
        a, w = (x, p["w"]) if dtype is None else \
            (_round(x, dtype), _round(p["w"], dtype))
        x = jnp.dot(a, w, precision=jax.lax.Precision.HIGHEST) \
            + p["b"].astype(jnp.float32)
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def group_softmax(space: Space, logits):
    return jnp.concatenate([jax.nn.softmax(g, axis=-1)
                            for g in space.groups(logits)], axis=-1)


def task_noise(seeds: np.ndarray, noise_dim: int):
    """Per-task noise: key PRNGKey(seed mod 2**32), sample 0 of its
    fold_in stream, uniform in [-0.1, 0.1)."""
    s = (np.asarray(seeds, np.int64) & np.int64(0xFFFFFFFF)).astype(np.uint32)
    keys = jax.vmap(jax.random.PRNGKey)(s)
    draw = lambda k: jax.random.uniform(jax.random.fold_in(k, 0),
                                        (1, noise_dim), jnp.float32,
                                        -0.1, 0.1)[0]
    return jax.vmap(draw)(keys)


def make_g_probs(space: Space, noise_dim: int, control: bool = False):
    @jax.jit
    def probs(params, net_enc, obj_enc, noise):
        x = jnp.concatenate([net_enc, obj_enc, noise], axis=-1)
        return group_softmax(space, mlp(params, x, control))
    return lambda params, net_enc, obj_enc, seeds: np.asarray(probs(
        params, net_enc, obj_enc, task_noise(seeds, noise_dim)))


# ---------------------------------------------------------------------------
# candidate enumeration and Algorithm 2
# ---------------------------------------------------------------------------
def employed_choices(space: Space, probs: np.ndarray, thresh: float,
                     cap: int) -> List[np.ndarray]:
    """Per group, the choices above the threshold (argmax always kept);
    while the product exceeds the cap, drop the non-argmax choice of least
    probability (ties: group-major, then choice order)."""
    groups = [np.asarray(g) for g in space.groups(probs)]
    employed = []
    for g in groups:
        keep = np.flatnonzero(g > thresh)
        employed.append(keep if keep.size else np.array([int(np.argmax(g))]))
    counts = [len(e) for e in employed]
    product = int(np.prod(counts, dtype=object))
    if product <= cap:
        return employed
    cand = []                     # (prob, group, choice) of droppable ones
    for gi, (g, e) in enumerate(zip(groups, employed)):
        am = int(np.argmax(g))
        cand += [(g[ci], gi, int(ci)) for ci in e if ci != am]
    order = sorted(range(len(cand)), key=lambda i: (cand[i][0], i))
    dropped = [set() for _ in groups]
    for i in order:
        if product <= cap:
            break
        _, gi, ci = cand[i]
        dropped[gi].add(ci)
        product = product // counts[gi] * (counts[gi] - 1)
        counts[gi] -= 1
    return [np.array([c for c in e if c not in d], np.int64)
            for e, d in zip(employed, dropped)]


def enumerate_candidates(space: Space, probs, thresh, cap) -> np.ndarray:
    """(C, n_dims) candidates in ``itertools.product`` order."""
    employed = employed_choices(space, probs, thresh, cap)
    c = int(np.prod([len(e) for e in employed]))
    flat = np.fromiter(itertools.chain.from_iterable(
        itertools.product(*[e.tolist() for e in employed])),
        dtype=np.int64, count=c * space.n_dims)
    return flat.reshape(c, space.n_dims)


def _updates(lat, pw, l_opt, p_opt, lo, po):
    """Algorithm 2's update test for every row under one carry."""
    fin = np.isfinite(lat) & np.isfinite(pw)
    if l_opt == 0.0 and p_opt == 0.0:                      # lines 7-8
        return fin
    if (l_opt > lo and p_opt > po) or (l_opt < lo and p_opt < po):
        return fin & (lat < l_opt) & (pw < p_opt)          # lines 10-13
    if l_opt > lo and p_opt < po:                          # lines 15-18
        return fin & (lat < l_opt) & (pw < po)
    if p_opt > po and l_opt < lo:                          # lines 20-22
        return fin & (pw < p_opt) & (lat < lo)
    return np.zeros(lat.shape, bool)


def algorithm2(lat, pw, lo, po) -> int:
    """Index chosen by the sequential Algorithm 2 (-1: none feasible).

    The carry changes only when a row is taken, so the next row the
    sequential scan takes is the first row after the last one taken whose
    update test holds under the current carry; each pass finds it at once.
    ``algorithm2_loop`` is the row-by-row scan it equals (tests compare)."""
    l_opt, p_opt, chosen, pos = 0.0, 0.0, -1, 0
    while pos < lat.shape[0]:
        hit = np.flatnonzero(_updates(lat[pos:], pw[pos:], l_opt, p_opt,
                                      lo, po)[:])
        if hit.size == 0:
            break
        i = pos + int(hit[0])
        l_opt, p_opt, chosen, pos = float(lat[i]), float(pw[i]), i, i + 1
    return chosen


def algorithm2_loop(lat, pw, lo, po) -> int:
    l_opt, p_opt, chosen = 0.0, 0.0, -1
    for i in range(lat.shape[0]):
        if _updates(lat[i:i + 1], pw[i:i + 1], l_opt, p_opt, lo, po)[0]:
            l_opt, p_opt, chosen = float(lat[i]), float(pw[i]), i
    return chosen


def satisfied(lat, pw, lo, po) -> bool:
    return bool(np.isfinite(lat) and np.isfinite(pw)
                and lat <= lo * (1 + NOISE_TOL) and pw <= po * (1 + NOISE_TOL))


def select(oracle: Oracle, net_idx, probs, thresh, cap, lo, po,
           control: bool = False):
    """One task -> (config indices or None, latency, power, satisfied,
    candidate count).  The control scores the chain in bfloat16 and the
    winner in float32."""
    cand = enumerate_candidates(oracle.space, probs, thresh, cap)
    lat, pw = oracle(np.broadcast_to(net_idx, (cand.shape[0], len(net_idx))),
                     cand)
    if control:
        bf = lambda a: np.asarray(a, ml_dtypes.bfloat16).astype(np.float64)
        lat, pw = bf(lat), bf(pw)
    i = algorithm2(lat, pw, float(lo), float(po))
    if i < 0:
        return None, np.inf, np.inf, False, cand.shape[0]
    l64, p64 = oracle(np.asarray(net_idx)[None], cand[i][None])
    l, p = float(l64[0]), float(p64[0])
    if control:
        l, p = float(np.float32(l)), float(np.float32(p))
    return cand[i], l, p, satisfied(l, p, lo, po), cand.shape[0]


# ---------------------------------------------------------------------------
# Algorithm 1: one training step of G and D
# ---------------------------------------------------------------------------
BIG = np.float32(3.4e38)


def make_alg1_step(cfg: dict, oracle: Oracle, control: bool = False):
    """Jitted (state, batch, rng) -> (state, rng, (loss_g, loss_d)), with
    state = (g, d, g_m, g_v, d_m, d_v, t).  Algorithm 1 of the paper: G's
    hard-decoded configuration is judged by the float64 oracle on the host
    (never differentiated); G descends the masked config cross-entropy plus
    w_critic times D's critic loss; then D descends its satisfaction loss
    on G's (old) probabilities; Adam (0.9, 0.999, 1e-8) on both.  The
    control keeps parameters and Adam's moments in bfloat16 and rounds
    each product's inputs to bfloat16."""
    space = oracle.space
    mode = "bf16" if control else False
    store = jnp.bfloat16 if control else jnp.float32
    w_critic, noise_dim = cfg["w_critic"], cfg["noise_dim"]

    def host_oracle(cfg_idx, net_idx):
        lat, pw = oracle(np.asarray(net_idx), np.asarray(cfg_idx))
        f = lambda a: np.nan_to_num(a.astype(np.float32), nan=BIG, posinf=BIG)
        return f(lat), f(pw)

    def judge(cfg_idx, net_idx):
        shape = jax.ShapeDtypeStruct((cfg_idx.shape[0],), jnp.float32)
        return jax.pure_callback(host_oracle, (shape, shape), cfg_idx,
                                 net_idx, vmap_method="sequential")

    def decode(probs):
        return jnp.stack([jnp.argmax(g, axis=-1) for g in space.groups(probs)],
                         axis=-1).astype(jnp.int32)

    def sat_ce(logits, sat):
        labels = jnp.stack([1.0 - sat, sat], axis=-1)
        return -jnp.sum(labels * jax.nn.log_softmax(logits, -1), axis=-1)

    def loss_g(g, d, b, noise):
        x = jnp.concatenate([b["net_enc"], b["obj_enc"], noise], axis=-1)
        probs = group_softmax(space, mlp(g, x, mode))
        lat, pw = judge(decode(probs), b["net_idx"])
        sat = jax.lax.stop_gradient(((lat <= b["lat_obj"])
                                     & (pw <= b["pow_obj"])).astype(jnp.float32))
        logits = mlp(d, jnp.concatenate([b["net_enc"], probs, b["obj_enc"]],
                                        -1), mode)
        critic = jnp.mean(sat_ce(logits, jnp.ones_like(sat)))
        ce_cfg = -jnp.sum(b["cfg_onehot"] * jnp.log(probs + 1e-9), axis=-1)
        return jnp.mean((1.0 - sat) * ce_cfg) + w_critic * critic, (probs, sat)

    def loss_d(d, b, probs, sat):
        x = jnp.concatenate([b["net_enc"], jax.lax.stop_gradient(probs),
                             b["obj_enc"]], -1)
        return jnp.mean(sat_ce(mlp(d, x, mode), sat))

    def adam(p, grad, m, v, t, lr):
        m = jax.tree.map(lambda m, g: (0.9 * m + 0.1 * g).astype(store), m, grad)
        v = jax.tree.map(lambda v, g: (0.999 * v + 0.001 * g * g).astype(store),
                         v, grad)
        bc1, bc2 = 1 - 0.9 ** t, 1 - 0.999 ** t
        p = jax.tree.map(lambda p, m, v: (p - lr * (m / bc1)
                                          / (jnp.sqrt(v / bc2) + 1e-8)
                                          ).astype(store), p, m, v)
        return p, m, v

    @jax.jit
    def step(state, b, rng):
        g, d, gm, gv, dm, dv, t = state
        rng, nrng = jax.random.split(rng)
        noise = jax.random.uniform(nrng, (b["net_enc"].shape[0], noise_dim),
                                   jnp.float32, -0.1, 0.1)
        (lg, (probs, sat)), gg = jax.value_and_grad(loss_g, has_aux=True)(
            g, d, b, noise)
        t = t + 1
        tf = t.astype(jnp.float32)
        g2, gm, gv = adam(g, gg, gm, gv, tf, cfg["g_lr"])
        ld, dg = jax.value_and_grad(loss_d)(d, b, probs, sat)
        d, dm, dv = adam(d, dg, dm, dv, tf, cfg["d_lr"])
        return (g2, d, gm, gv, dm, dv, t), rng, (lg, ld), (gg, dg)

    def init_state(g, d):
        cast = lambda tree: jax.tree.map(lambda a: a.astype(store), tree)
        zeros = lambda tree: jax.tree.map(
            lambda a: jnp.zeros(a.shape, store), tree)
        return (cast(g), cast(d), zeros(g), zeros(g), zeros(d), zeros(d),
                jnp.zeros((), jnp.int32))

    return step, init_state


def epoch_perms(n: int, batch: int, seed, epochs: int) -> List[np.ndarray]:
    """Each epoch's (n // batch, batch) row order: one permutation per
    epoch from numpy's generator on the call's seed, cut to whole batches."""
    rng = np.random.default_rng(seed)
    nb = n // batch
    return [rng.permutation(n)[:nb * batch].reshape(nb, batch)
            for _ in range(epochs)]
