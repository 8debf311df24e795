"""The fused-MLP fast path, end to end on CPU via interpret mode.

The dispatch rule (kernels/dispatch.py) keeps CPU CI on the jnp reference,
so these tests drive the *actual Pallas kernels* through the jitted
consumers with ``dispatch.force_interpret()`` — the same kernel code TPU
compiles — and pin:

- Algorithm 1: one fused train step == one unfused step (params, metrics);
- Explorer: the megakernel (chained) G forward == the vmap route;
- LargeMLP baseline: same for its noise-averaged forward;
- nn.mlp_apply: the non-ReLU-activation contract (raise on explicit
  use_fused=True, honored fallback on auto) and fused/unfused parity;
- DSEServer: the kernel report follows each engine's own setting.

Caution for new tests: ``_cached_fwd`` memoizes jitted forwards on
(space, gan_cfg, chained) — traces taken under force_interpret stay
interpret-routed for that key, so interpret-mode traces here always use a
config with ``use_fused=True`` (a key the non-interpret tests never use).
"""
import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gan as G
from repro.core import train as T
from repro.core.dse_api import GANDSE
from repro.core.explorer import (_cached_fwd, _task_probs, task_keys,
                                 task_seeds)
from repro.dataset.generator import generate_dataset
from repro.design_models.dnnweaver import DnnWeaverModel
from repro.kernels import dispatch as D
from repro.nn import layers as L


@pytest.fixture(scope="module")
def setup(small_dataset):
    model = DnnWeaverModel()
    cfg = G.GANConfig(n_net=model.net_space.n_dims).scaled(
        layers=2, neurons=32, batch_size=32, lr=1e-3)
    ds = small_dataset(model, n=128)
    rng = jax.random.PRNGKey(0)
    r1, r2, r3 = jax.random.split(rng, 3)
    gp = G.init_generator(r1, cfg, model.space)
    dp = G.init_discriminator(r2, cfg, model.space)
    batch = {k: jnp.asarray(v)
             for k, v in T.encode_batch(model, ds, np.arange(32)).items()}
    return model, cfg, ds, gp, dp, batch, r3


def _tree_close(a, b, rtol=1e-4, atol=1e-5):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=rtol, atol=atol), a, b)


# ---------------------------------------------------------------------------
# nn.mlp_apply contract (the old silent-ignore bug)
# ---------------------------------------------------------------------------
def test_mlp_apply_fused_rejects_non_relu(rng):
    params = L.mlp_init(jax.random.PRNGKey(0), 8, [16, 16], 4)
    x = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    with pytest.raises(ValueError, match="relu"):
        L.mlp_apply(params, x, activation=jnp.tanh, use_fused=True)


def test_mlp_apply_auto_falls_back_for_non_relu(rng):
    """use_fused=None + non-ReLU activation: the activation is honored via
    the unfused path (it used to be silently replaced by ReLU when the
    fused route was taken)."""
    params = L.mlp_init(jax.random.PRNGKey(0), 8, [16, 16], 4)
    x = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    got = L.mlp_apply(params, x, activation=jnp.tanh)
    h = x
    for p in params["layers"][:-1]:
        h = jnp.tanh(h @ p["w"] + p["b"])
    want = h @ params["layers"][-1]["w"] + params["layers"][-1]["b"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # even under the interpret hook, on auto or pinned to jnp, the
    # fallback holds — the activation must never be replaced by the
    # kernel's ReLU
    with D.force_interpret():
        got2 = L.mlp_apply(params, x, activation=jnp.tanh)
        got3 = L.mlp_apply(params, x, activation=jnp.tanh, use_fused=False)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got3), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_mlp_apply_fused_interpret_parity(rng):
    params = L.mlp_init(jax.random.PRNGKey(1), 12, [24, 24], 6)
    x = jnp.asarray(rng.normal(size=(7, 12)), jnp.float32)
    want = L.mlp_apply(params, x)
    with D.force_interpret():
        got = L.mlp_apply(params, x, use_fused=True)
        chained = L.mlp_apply_chained(params, x, use_fused=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(chained), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Algorithm 1 through the fused kernels
# ---------------------------------------------------------------------------
def test_train_step_fused_interpret_parity(setup):
    """One full Algorithm 1 step (G and D updates, so forward AND custom
    -vjp backward kernels) in interpret-fused mode == the jnp step."""
    model, cfg, ds, gp, dp, batch, rng = setup

    def one_step():
        g_optim, d_optim, step = T.make_train_step(model, cfg)
        go, do = g_optim.init(gp), d_optim.init(dp)
        return step(gp, dp, go, do, batch, rng)

    g_ref, d_ref, *_, m_ref = one_step()
    # spy on the kernel entry so this test can never silently degrade into
    # comparing the jnp route against itself
    import repro.kernels.fused_mlp as FM
    orig, seen = FM.fused_dense, []
    FM.fused_dense = lambda *a, **k: (seen.append(k), orig(*a, **k))[1]
    try:
        with D.force_interpret():
            g_fus, d_fus, *_, m_fus = one_step()
    finally:
        FM.fused_dense = orig
    assert seen and all(k.get("interpret") for k in seen), \
        "the fused-interpret route was not engaged"
    for k in m_ref:
        np.testing.assert_allclose(float(m_ref[k]), float(m_fus[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)
    _tree_close(g_ref, g_fus)
    _tree_close(d_ref, d_fus)


# ---------------------------------------------------------------------------
# Explorer inference routes
# ---------------------------------------------------------------------------
def test_explorer_chained_route_parity(setup):
    """The flattened megakernel route == the vmap route (same per-task
    noise streams), on both the jnp fallback and the interpret kernels."""
    model, cfg, ds, gp, dp, batch, rng = setup
    net_enc = jnp.asarray(ds.net_encoded(model, ds.net_idx[:5]))
    obj_enc = jnp.asarray(ds.obj_encoded(ds.latency[:5], ds.power[:5]))
    seeds = task_seeds(7, 5)

    p_vmap = _cached_fwd(model.space, cfg, chained=False)(
        gp, net_enc, obj_enc, seeds, n_samples=3)
    p_chain = _cached_fwd(model.space, cfg, chained=True)(
        gp, net_enc, obj_enc, seeds, n_samples=3)
    np.testing.assert_allclose(np.asarray(p_vmap), np.asarray(p_chain),
                               rtol=1e-5, atol=1e-6)

    fused_cfg = dataclasses.replace(cfg, use_fused=True)
    with D.force_interpret():
        p_kernel = _cached_fwd(model.space, fused_cfg, chained=True)(
            gp, net_enc, obj_enc, seeds, n_samples=3)
    np.testing.assert_allclose(np.asarray(p_vmap), np.asarray(p_kernel),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("route", ["vmap", "chained-interpret"])
@pytest.mark.parametrize("seed", [
    7, -5, 2**31 + 3, 2**40 + 11,
    np.array([-3, 2**33, 5, 2**31 - 1, -2**40], np.int64)],
    ids=["7", "negative", "2^31+3", "2^40+11", "per-row"])
def test_forward_keys_in_program_match_task_keys(setup, route, seed):
    """The forward derives each task's key from its uint32 seed inside the
    program; its probs equal, bit for bit, the same forward body fed the
    host-built `task_keys`, for every seed form `task_keys` accepts."""
    model, cfg, ds, gp, dp, batch, rng = setup
    net_enc = ds.net_encoded(model, ds.net_idx[:5])
    obj_enc = ds.obj_encoded(ds.latency[:5], ds.power[:5])
    chained = route == "chained-interpret"
    if chained:
        cfg = dataclasses.replace(cfg, use_fused=True)
    with D.force_interpret() if chained else contextlib.nullcontext():
        new = _cached_fwd(model.space, cfg, chained=chained)(
            gp, net_enc, obj_enc, task_seeds(seed, 5), n_samples=3)
        old = jax.jit(functools.partial(_task_probs, model.space, cfg,
                                        chained, None),
                      static_argnames="n_samples")(
            gp, net_enc, obj_enc, task_keys(seed, 5), n_samples=3)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_large_mlp_chained_route_parity(rng):
    from repro.baselines.mlp import LargeMLP, _cached_fwd as mlp_fwd
    from repro.design_models.dnnweaver import DnnWeaverModel

    model = DnnWeaverModel()
    mlp = LargeMLP(model, hidden_layers=2, neurons=24)
    params = mlp.init_params(seed=0)
    t = 4
    net_enc = jnp.asarray(rng.normal(size=(t, model.net_space.n_dims)),
                          jnp.float32)
    obj_enc = jnp.asarray(rng.normal(size=(t, 2)), jnp.float32)
    keys = task_keys(11, t)
    _, f_vmap = mlp_fwd(model.space, mlp.noise_dim, None, False)
    _, f_chain = mlp_fwd(model.space, mlp.noise_dim, None, True)
    p1 = f_vmap(params, net_enc, obj_enc, keys, n_samples=2)
    p2 = f_chain(params, net_enc, obj_enc, keys, n_samples=2)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2),
                               rtol=1e-5, atol=1e-6)
    with D.force_interpret():
        _, f_kernel = mlp_fwd(model.space, mlp.noise_dim, True, True)
        p3 = f_kernel(params, net_enc, obj_enc, keys, n_samples=2)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p3),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# serve-layer kernel report
# ---------------------------------------------------------------------------
def test_serve_kernel_report_follows_engine(setup):
    """summary()["kernels"] reports the route each engine's own
    ``gan_cfg.use_fused`` takes under the dispatch rule (the server sets
    none), and the served answer equals a direct dispatch."""
    from repro.dataset.generator import DSETask
    from repro.serve import DSEServer, ServeConfig

    model, cfg, ds, gp, dp, batch, rng = setup
    for use_fused in (None, True, False):
        engine = GANDSE(model, dataclasses.replace(cfg, use_fused=use_fused))
        engine.attach(ds, gp)
        srv = DSEServer(ServeConfig(max_batch=4))
        srv.register(engine)
        assert engine.gan_cfg.use_fused is use_fused
        assert "backend" in srv.summary()["kernels"]
        for hook in (False, True):
            with D.force_interpret(hook):
                got = srv.summary()["kernels"]["fused"][model.name]
                assert got is D.kernel_route_active(use_fused)
                # off the TPU only the hook reaches the kernels, and an
                # explicit False stays on the jnp route even then
                assert got is (hook and use_fused is not False)
    # the engine pinned to the jnp route serves: same Selection as a
    # direct dispatch through the same batched route
    rid = srv.submit(model.name, ds.net_idx[0], float(ds.latency[0] * 2),
                     float(ds.power[0] * 2), seed=3)
    srv.drain()
    resp = srv.response(rid)
    assert resp is not None and resp.result is not None
    task = DSETask.single(ds.net_idx[0], float(ds.latency[0] * 2),
                          float(ds.power[0] * 2))
    want = engine.explore_tasks(task, seed=3)[0]
    np.testing.assert_array_equal(resp.result.selection.cfg_idx,
                                  want.selection.cfg_idx)
    assert resp.result.selection.satisfied == want.selection.satisfied
