"""Batched-vs-sequential exploration parity — the `explore_batch` contract.

`GANDSE.explore_batch` must return the same Selection (cfg_idx, latency,
power, satisfied, n_candidates) as the looped `explore`, for all three
design models, including tasks with zero feasible candidates and ragged
candidate counts across the batch.  Also pins the device candidate
enumeration to the host route and the (T, C) oracle broadcast contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gan as G
from repro.core.dse_api import GANDSE
from repro.core.explorer import (_PROD_LIM, ExplorerConfig,
                                 enumerate_candidates)
from repro.dataset.generator import generate_tasks
from repro.design_models.base import DesignModel
from repro.design_models.dnnweaver import DnnWeaverModel
from repro.design_models.im2col import Im2colModel
from repro.design_models.tpu_mesh import TpuMeshModel

MODELS = {m.name: m for m in (DnnWeaverModel, Im2colModel, TpuMeshModel)}


@pytest.fixture(scope="module")
def models():
    """Shared instances: the per-instance Algorithm 2 jit caches survive
    across this module's tests, keeping tier-1 compile time down."""
    return {name: cls() for name, cls in MODELS.items()}


def _attached(model, tiny_gan_cfg, small_dataset, thresh=0.1, cap=128,
              ds_model=None):
    """GANDSE with a random-init generator: exploration parity does not
    depend on training quality, and skipping train() keeps tier-1 fast."""
    cfg = tiny_gan_cfg(model)
    g = GANDSE(model, cfg,
               ExplorerConfig(prob_threshold=thresh, max_candidates=cap))
    ds = small_dataset(ds_model or model, n=256)
    g.attach(ds, G.init_generator(jax.random.PRNGKey(3), cfg, model.space))
    return g


def _assert_selection_equal(name, i, sa, sb):
    assert sa.n_candidates == sb.n_candidates, (name, i)
    assert (sa.cfg_idx is None) == (sb.cfg_idx is None), (name, i)
    if sa.cfg_idx is not None:
        np.testing.assert_array_equal(sa.cfg_idx, sb.cfg_idx, err_msg=f"{name}[{i}]")
    assert sa.latency == sb.latency and sa.power == sb.power, (name, i)
    assert sa.satisfied == sb.satisfied, (name, i)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_explore_batch_matches_sequential(name, models, tiny_gan_cfg,
                                          small_dataset):
    model = models[name]
    g = _attached(model, tiny_gan_cfg, small_dataset)
    tasks = generate_tasks(model, 6, seed=2)
    batched = g.explore_batch(tasks, seed=7)
    seq = [g.explore(tasks.net_idx[i], tasks.lat_obj[i], tasks.pow_obj[i],
                     seed=7 + i) for i in range(6)]
    counts = {r.selection.n_candidates for r in batched}
    assert len(counts) > 1, "seeds no longer produce ragged candidate counts"
    for i, (a, b) in enumerate(zip(batched, seq)):
        _assert_selection_equal(name, i, a.selection, b.selection)
    # explore_tasks routes through the same batched path by default
    routed = g.explore_tasks(tasks, seed=7)
    for i, (a, b) in enumerate(zip(routed, batched)):
        _assert_selection_equal(name, i, a.selection, b.selection)


class _InfeasibleModel(DnnWeaverModel):
    """Every config infeasible: the zero-feasible-candidates edge case."""

    name = "dnnweaver_infeasible"

    def evaluate(self, net, config):
        lat, pw = super().evaluate(net, config)
        return np.full_like(lat, np.inf), np.full_like(pw, np.inf)

    def evaluate_jax(self, net, config):
        lat, pw = super().evaluate_jax(net, config)
        return jnp.full_like(lat, jnp.inf), jnp.full_like(pw, jnp.inf)


def test_explore_batch_zero_feasible(models, tiny_gan_cfg, small_dataset):
    # T=6 / seed=2 on the dnnweaver space: identical shapes to the parity
    # test above, so the enumeration/forward programs are jit-cache hits
    model = _InfeasibleModel()
    g = _attached(model, tiny_gan_cfg, small_dataset,
                  ds_model=models["dnnweaver"])
    tasks = generate_tasks(models["dnnweaver"], 6, seed=2)
    batched = g.explore_batch(tasks, seed=7)
    seq = [g.explore(tasks.net_idx[i], tasks.lat_obj[i], tasks.pow_obj[i],
                     seed=7 + i) for i in range(6)]
    for i, (a, b) in enumerate(zip(batched, seq)):
        _assert_selection_equal("infeasible", i, a.selection, b.selection)
        assert a.selection.cfg_idx is None and not a.selection.satisfied
        assert a.selection.n_candidates > 0      # candidates existed...
        assert a.selection.latency == np.inf     # ...none were feasible


class _HostOnlyModel(DnnWeaverModel):
    """jnp oracle hidden: exercises the automatic sequential fallback."""

    name = "dnnweaver_host_only"
    evaluate_jax = DesignModel.evaluate_jax


def test_explore_batch_falls_back_without_jax_oracle(models, tiny_gan_cfg,
                                                     small_dataset):
    model = _HostOnlyModel()
    assert not model.has_jax_oracle
    g = _attached(model, tiny_gan_cfg, small_dataset,
                  ds_model=models["dnnweaver"])
    tasks = generate_tasks(models["dnnweaver"], 6, seed=2)
    batched = g.explore_batch(tasks, seed=7)
    seq = [g.explore(tasks.net_idx[i], tasks.lat_obj[i], tasks.pow_obj[i],
                     seed=7 + i) for i in range(6)]
    for i, (a, b) in enumerate(zip(batched, seq)):
        _assert_selection_equal("host_only", i, a.selection, b.selection)
    assert any(r.selection.cfg_idx is not None for r in batched)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_enumeration_batch_matches_host(name, models):
    """The fused route's device enumeration masks (``_enum_core``) keep
    exactly the host route's per-group choice sets, and count exactly its
    candidates, per task, across thresholds and caps (including
    trim-forcing caps)."""
    from repro.core.explorer import _enum_core, _trimmed_employed

    space = models[name].space
    rng = np.random.default_rng(0)
    probs = np.stack([
        np.concatenate([rng.dirichlet(np.ones(d.n) * rng.uniform(0.3, 3.0))
                        for d in space.dims]).astype(np.float32)
        for _ in range(6)       # T=6 everywhere: shapes hit the jit cache
    ])
    masks = jax.jit(_enum_core(space)[0])
    for thresh, cap in [(0.2, 4096), (0.05, 64), (0.02, 1)]:
        keep, counts, total = jax.device_get(
            masks(probs, np.float32(thresh), np.int32(cap)))
        for t in range(probs.shape[0]):
            host = enumerate_candidates(space, probs[t], thresh, cap)
            assert total[t] == host.shape[0], (thresh, cap)
            employed = _trimmed_employed(space, probs[t], thresh, cap)
            for g, e in enumerate(employed):
                np.testing.assert_array_equal(np.flatnonzero(keep[t, g]), e)
                assert counts[t, g] == len(e)


@pytest.mark.parametrize("n_groups,cap", [(8, 2 ** 20), (10, _PROD_LIM)])
def test_enumeration_trim_at_cap_limit(n_groups, cap):
    """A large cap (2**20) and the route limit (2**26) must still trim on
    device: the product clamp sits strictly above the cap (regression:
    clamping AT the cap made `> cap` unsatisfiable, disabling the trim and
    allocating the untrimmed cartesian product).  8 groups of 8 (2**24)
    overflow 2**20, 10 groups (2**30) the route limit; checked at the
    mask level so the test never materializes a candidate set."""
    from repro.core.encoding import ConfigDim, ConfigSpace
    from repro.core.explorer import _enum_core, _trimmed_employed

    space = ConfigSpace(dims=tuple(
        ConfigDim(f"d{i}", tuple(float(j) for j in range(8)))
        for i in range(n_groups)))               # product 8**n >> cap
    rng = np.random.default_rng(0)
    probs = np.concatenate([rng.dirichlet(np.ones(8))
                            for _ in range(n_groups)]
                           ).astype(np.float32)[None]
    assert 8 ** n_groups > cap                   # the trim must engage
    masks_fn = jax.jit(_enum_core(space)[0])
    keep, counts, total = masks_fn(jnp.asarray(probs), jnp.float32(0.01),
                                   jnp.int32(cap))
    total = int(np.asarray(total)[0])
    employed = _trimmed_employed(space, probs[0], 0.01, cap)
    want = 1
    for e in employed:
        want *= len(e)
    assert want <= cap and total == want
    keep = np.asarray(keep[0])
    for g, e in enumerate(employed):
        np.testing.assert_array_equal(np.flatnonzero(keep[g]), e)


def test_task_keys_survive_large_seeds(models, tiny_gan_cfg, small_dataset):
    """Per-task noise keys must come from a host int64 sum: the legacy
    `seed + jnp.arange(T)` int32 route raised OverflowError for Python-int
    seeds >= 2**31 and aliased wrapped sums with other seeds' keys."""
    from repro.core.explorer import task_keys

    # bitwise parity with the legacy int32 route wherever it worked
    for seed in (0, 7, 12345, 2**31 - 9):
        legacy = jax.vmap(jax.random.PRNGKey)(seed + jnp.arange(8))
        np.testing.assert_array_equal(np.asarray(task_keys(seed, 8)),
                                      np.asarray(legacy))
    # seeds >= 2**31 used to raise at dispatch; now valid and collision-free
    big = 2**31
    keys = np.asarray(task_keys(big, 8))
    assert len({tuple(k) for k in keys}) == 8
    # the batched-vs-sequential parity contract extends to large seeds
    g = _attached(models["dnnweaver"], tiny_gan_cfg, small_dataset)
    tasks = generate_tasks(models["dnnweaver"], 6, seed=2)
    batched = g.explore_batch(tasks, seed=big)
    for i in (0, 3, 5):
        r = g.explore(tasks.net_idx[i], tasks.lat_obj[i], tasks.pow_obj[i],
                      seed=big + i)
        _assert_selection_equal("large_seed", i, batched[i].selection,
                                r.selection)


@pytest.mark.parametrize("seed", [2**31 + 5,
                                  np.array([-7, 2**40, 3, 9, 2**33, 1])],
                         ids=["scalar", "per-row"])
def test_warm_explore_batch_runs_no_eager_jax(models, tiny_gan_cfg,
                                              small_dataset, monkeypatch,
                                              seed):
    """A warm engine call is numpy preparation plus the two jitted
    programs: no eager `task_keys`, no Python-level transfer in
    `put_sharded` without a mesh, and no new entry in either program's
    cache for new seeds.  Its Selections still equal the sequential
    route's."""
    from repro.core import explorer as E
    from repro.core import shard

    model = models["dnnweaver"]
    g = _attached(model, tiny_gan_cfg, small_dataset)
    tasks = generate_tasks(model, 6, seed=4)
    g.explore_batch(generate_tasks(model, 8, seed=11), seed=101)
    fwd = g._explorer._fwd
    run = model.__dict__["_fused_select"][g.explorer_cfg.select_tile]
    sizes = fwd._cache_size(), run._cache_size()

    def refuse(*a, **k):
        raise AssertionError("warm engine call built keys eagerly")

    calls = []

    def counted(fn):
        def spy(*a, **k):
            calls.append(fn.__name__)
            return fn(*a, **k)
        return spy

    assert shard.get_task_mesh() is None
    monkeypatch.setattr(E, "task_keys", refuse)
    monkeypatch.setattr(jnp, "asarray", counted(jnp.asarray))
    monkeypatch.setattr(jax, "device_put", counted(jax.device_put))
    batched = g.explore_batch(tasks, seed=seed)
    assert calls == []
    assert (fwd._cache_size(), run._cache_size()) == sizes
    monkeypatch.undo()

    seeds = E.row_seeds(seed, 6)
    for i in range(6):
        r = g.explore(tasks.net_idx[i], tasks.lat_obj[i], tasks.pow_obj[i],
                      seed=seeds[i])
        _assert_selection_equal("no_eager", i, batched[i].selection,
                                r.selection)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_oracle_broadcasts_task_by_candidate_grids(name, models):
    """(T, 1, n_net) x (T, C, n_cfg) -> (T, C): one grid call equals the
    stacked per-task calls, on both the jnp and numpy oracles.  (Eager jnp:
    the jitted grid shape is compiled and exercised by the fused select in
    test_explore_batch_matches_sequential; this pins the broadcast math
    without paying two more XLA compiles per model.)"""
    model = models[name]
    rng = np.random.default_rng(1)
    T, C = 4, 16
    net_idx = model.net_space.sample_indices(rng, T)
    cfg_idx = np.stack([model.space.sample_indices(rng, C) for _ in range(T)])
    latj, pwj = model.evaluate_jax_indices(jnp.asarray(net_idx[:, None, :]),
                                           jnp.asarray(cfg_idx))
    lat, pw = model.evaluate_indices(net_idx[:, None, :], cfg_idx)
    assert latj.shape == pwj.shape == lat.shape == (T, C)
    for t in range(T):
        lat_t, pw_t = model.evaluate_indices(
            np.repeat(net_idx[t][None], C, axis=0), cfg_idx[t])
        np.testing.assert_array_equal(lat[t], lat_t)
        np.testing.assert_array_equal(pw[t], pw_t)
        latj_t, pwj_t = model.evaluate_jax_indices(
            jnp.asarray(net_idx[t][None]), jnp.asarray(cfg_idx[t]))
        np.testing.assert_array_equal(np.asarray(latj[t]), np.asarray(latj_t))
        np.testing.assert_array_equal(np.asarray(pwj[t]), np.asarray(pwj_t))
