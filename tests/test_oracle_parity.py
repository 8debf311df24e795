"""Parity of the fused jnp oracles with the host numpy design models, and
regression of the scanned train loop against the per-batch stepwise loop.

These guard the device-resident Algorithm 1 hot path: if a jnp port drifts
from its numpy twin, or the epoch scan stops reproducing the stepwise
update sequence, the reproduction silently trains against a different
design model than it reports.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gan as G
from repro.core.train import (encode_batch, make_epoch_fn, make_oracle,
                              make_train_step, train_gan)
from repro.dataset.generator import generate_dataset
from repro.design_models.base import DesignModel
from repro.design_models.dnnweaver import DnnWeaverModel
from repro.design_models.im2col import Im2colModel
from repro.design_models.tpu_mesh import DeepSeekV3Mesh, TpuMeshModel

MODELS = {m.name: m for m in (DnnWeaverModel, Im2colModel, TpuMeshModel,
                              DeepSeekV3Mesh)}


# ---------------------------------------------------------------------------
# evaluate_jax == evaluate
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(MODELS))
def test_evaluate_jax_matches_numpy(name):
    model = MODELS[name]()
    assert model.has_jax_oracle
    oracle = jax.jit(model.evaluate_jax_indices)
    rng = np.random.default_rng(0)
    lat_all = []
    for seed in range(2):                      # randomized nets AND configs
        net_idx = model.net_space.sample_indices(rng, 256)
        cfg_idx = model.space.sample_indices(rng, 256)
        lat, pw = model.evaluate_indices(net_idx, cfg_idx)
        latj, pwj = oracle(jnp.asarray(net_idx), jnp.asarray(cfg_idx))
        latj = np.asarray(latj, np.float64)
        pwj = np.asarray(pwj, np.float64)
        fin = np.isfinite(lat)
        # feasibility masks (incl. the +inf rows) agree exactly
        np.testing.assert_array_equal(np.isfinite(latj), fin)
        np.testing.assert_array_equal(np.isfinite(pwj), np.isfinite(pw))
        np.testing.assert_allclose(latj[fin], lat[fin], rtol=1e-5)
        np.testing.assert_allclose(pwj[fin], pw[fin], rtol=1e-5)
        lat_all.append(lat)
    if name != "dnnweaver":    # dnnweaver's derived tiles always fit
        assert not np.isfinite(np.concatenate(lat_all)).all(), \
            "sample contained no infeasible rows; +inf parity untested"


def test_evaluate_jax_known_infeasible_is_inf():
    """The hand-built infeasible im2col config is +inf on both routes."""
    model = Im2colModel()
    net = np.array([[256., 256., 64., 64., 5., 5.]])
    cfg = np.array([[4096., 512., 512., 256., 256., 256.,
                     128., 128., 256., 256., 5., 5.]])
    lat, pw = model.evaluate(net, cfg)
    latj, pwj = model.evaluate_jax(jnp.asarray(net), jnp.asarray(cfg))
    assert np.isinf(lat[0]) and np.isinf(pw[0])
    assert np.isinf(float(latj[0])) and np.isinf(float(pwj[0]))


def test_make_oracle_fused_requires_jnp_port():
    class HostOnly(DesignModel):
        name = "host_only"

        def __init__(self):
            m = DnnWeaverModel()
            self.space, self.net_space = m.space, m.net_space

        def evaluate(self, net, config):
            return np.ones(net.shape[0]), np.ones(net.shape[0])

    host = HostOnly()
    assert not host.has_jax_oracle
    _, fused = make_oracle(host)               # auto: falls back to callback
    assert not fused
    with pytest.raises(ValueError):
        make_oracle(host, use_jax_oracle=True)
    _, fused = make_oracle(DnnWeaverModel())   # auto: picks the jnp route
    assert fused


# ---------------------------------------------------------------------------
# Algorithm 2: device scan == host loop
# ---------------------------------------------------------------------------
def test_select_jax_matches_host_loop():
    from repro.core.selector import select

    model = DnnWeaverModel()
    rng = np.random.default_rng(7)
    for _ in range(8):     # enough draws to hit several pow2 pad buckets
        net = model.net_space.sample_indices(rng, 1)[0]
        n_cand = int(rng.integers(1, 80))
        cands = model.space.sample_indices(rng, n_cand).astype(np.int32)
        lat, pw = model.evaluate_indices(
            np.repeat(net[None], n_cand, axis=0), cands)
        # 5% off the quantiles so no objective ties a candidate metric
        # exactly (a tie makes the strict-< chain precision-dependent)
        lo = float(np.quantile(lat, 0.4) * 1.05)
        po = float(np.quantile(pw, 0.6) * 1.05)
        a = select(model, net, cands, lo, po, use_jax=True)
        b = select(model, net, cands, lo, po, use_jax=False)
        assert a.satisfied == b.satisfied
        assert a.n_candidates == b.n_candidates
        np.testing.assert_allclose(a.latency, b.latency, rtol=1e-5)
        np.testing.assert_allclose(a.power, b.power, rtol=1e-5)
        if b.cfg_idx is None:
            assert a.cfg_idx is None
        else:
            np.testing.assert_array_equal(a.cfg_idx, b.cfg_idx)


def test_select_jax_accepts_2d_net_idx():
    """The host route atleast_2d's net_idx; the device route must accept
    the same (1, n_net_dims) shape (auto-routes there for large sets)."""
    from repro.core.selector import select

    model = DnnWeaverModel()
    rng = np.random.default_rng(0)
    net = model.net_space.sample_indices(rng, 1)        # (1, n_dims)
    cands = model.space.sample_indices(rng, 600).astype(np.int32)
    a = select(model, net, cands, 1e-3, 2.0, use_jax=True)
    b = select(model, net, cands, 1e-3, 2.0, use_jax=False)
    assert a.satisfied == b.satisfied
    np.testing.assert_allclose(a.latency, b.latency, rtol=1e-5)


# ---------------------------------------------------------------------------
# the fused step really has no host callback in its program
# ---------------------------------------------------------------------------
def _step_jaxpr(model, cfg, use_jax_oracle):
    ds = generate_dataset(model, 64, seed=0)
    rng = jax.random.PRNGKey(0)
    g_params = G.init_generator(jax.random.fold_in(rng, 1), cfg, model.space)
    d_params = G.init_discriminator(jax.random.fold_in(rng, 2), cfg, model.space)
    g_optim, d_optim, step = make_train_step(model, cfg,
                                             use_jax_oracle=use_jax_oracle)
    batch = {k: jnp.asarray(v)
             for k, v in encode_batch(model, ds, np.arange(32)).items()}
    return str(jax.make_jaxpr(step)(
        g_params, d_params, g_optim.init(g_params), d_optim.init(d_params),
        batch, rng))


def test_fused_step_has_no_pure_callback():
    model = DnnWeaverModel()
    cfg = G.GANConfig(n_net=model.net_space.n_dims).scaled(
        layers=1, neurons=16, batch_size=32)
    assert "pure_callback" not in _step_jaxpr(model, cfg, None)
    # sanity: the forced-callback route really does go through the host
    assert "pure_callback" in _step_jaxpr(model, cfg, False)


# ---------------------------------------------------------------------------
# scanned epoch == stepwise loop (the seed implementation's trajectory)
# ---------------------------------------------------------------------------
def test_scanned_train_matches_stepwise_loop(tiny_gan_cfg, small_dataset):
    model = DnnWeaverModel()
    ds = small_dataset(model, n=256)
    cfg = tiny_gan_cfg(model, neurons=32, batch_size=64)
    iters, bs = 2, 64

    st = train_gan(model, ds, cfg, iters=iters, seed=0)

    # seed-style reference: one jitted step per batch, host re-encoding,
    # identical rng split and permutation sequence.
    rng = jax.random.PRNGKey(0)
    rng, g_rng, d_rng = jax.random.split(rng, 3)
    g_params = G.init_generator(g_rng, cfg, model.space)
    d_params = G.init_discriminator(d_rng, cfg, model.space)
    g_optim, d_optim, step = make_train_step(model, cfg)
    g_opt, d_opt = g_optim.init(g_params), d_optim.init(d_params)
    np_rng = np.random.default_rng(0)
    ref = []
    for _ in range(iters):
        perm = np_rng.permutation(ds.n)
        for b0 in range(0, ds.n - bs + 1, bs):
            batch = {k: jnp.asarray(v) for k, v in
                     encode_batch(model, ds, perm[b0:b0 + bs]).items()}
            (g_params, d_params, g_opt, d_opt, rng, m) = step(
                g_params, d_params, g_opt, d_opt, batch, rng)
            ref.append({k: float(v) for k, v in m.items()})

    assert len(st.history) == len(ref)
    for got, want in zip(st.history, ref):
        for k, v in want.items():
            np.testing.assert_allclose(got[k], v, rtol=2e-3, atol=1e-4,
                                       err_msg=k)
    # final params agree too (same update sequence, different program)
    leaves = zip(jax.tree.leaves(st.g_params), jax.tree.leaves(g_params))
    for a, b in leaves:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-4)


def test_callback_and_fused_training_agree(tiny_gan_cfg, small_dataset):
    """The oracle switch changes the execution route, not the math."""
    model = DnnWeaverModel()
    ds = small_dataset(model, n=256)
    cfg = tiny_gan_cfg(model, neurons=16, batch_size=64)
    a = train_gan(model, ds, cfg, iters=1, seed=0, use_jax_oracle=True)
    b = train_gan(model, ds, cfg, iters=1, seed=0, use_jax_oracle=False)
    for ra, rb in zip(a.history, b.history):
        for k in ra:
            np.testing.assert_allclose(ra[k], rb[k], rtol=2e-3, atol=1e-4,
                                       err_msg=k)
