"""The plain reference against the program on the CPU, at small sizes:
they must agree exactly where both compute in float32 or float64 alike."""
import itertools
import os

import jax
import numpy as np
import pytest

from chipbench import harness, reference


def _cfg(name):
    return harness.load_json(f"{harness.HERE}/configs/{name}.json")


CONFIGS = harness.benchmark()["configs"]


@pytest.mark.parametrize("entry", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_oracle_matches_program(entry):
    """Every configuration's reference oracle gives its program's numpy
    oracle's numbers exactly."""
    import importlib
    cfg = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
    mod, cls = cfg["program_model"].split(":")
    model = getattr(importlib.import_module(mod), cls)()
    oracle = reference.Oracle(cfg)
    rng = np.random.default_rng(0)
    net, c = oracle.net_space.sample(rng, 4096), oracle.space.sample(rng, 4096)
    got = oracle(net, c)
    want = model.evaluate_indices(net, c)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_algorithm2_jump_equals_loop():
    rng = np.random.default_rng(1)
    for _ in range(200):
        n = int(rng.integers(1, 300))
        lat = rng.choice([1.0, 2.0, 3.0, np.inf], size=n) * rng.uniform(.5, 2, n)
        pw = rng.choice([1.0, 2.0, np.inf], size=n) * rng.uniform(.5, 2, n)
        lo, po = rng.uniform(0.5, 3), rng.uniform(0.5, 3)
        assert reference.algorithm2(lat, pw, lo, po) == \
            reference.algorithm2_loop(lat, pw, lo, po)


@pytest.mark.parametrize("thresh,cap", [(0.0, 64), (0.05, 512), (0.2, 4096)])
def test_enumeration_matches_program(thresh, cap):
    from repro.core.explorer import enumerate_candidates
    from repro.design_models.im2col import Im2colModel
    model = Im2colModel()
    space = reference.Oracle(_cfg("gandse-im2col")).space
    rng = np.random.default_rng(2)
    for _ in range(20):
        logits = rng.normal(size=space.onehot_width) * 2
        probs = np.concatenate([np.exp(g) / np.exp(g).sum()
                                for g in space.groups(logits)]).astype(np.float32)
        want = enumerate_candidates(model.space, probs, thresh, cap)
        got = reference.enumerate_candidates(space, probs, thresh, cap)
        np.testing.assert_array_equal(got, want)


def test_select_matches_program_host_route():
    from repro.core.selector import select
    from repro.design_models.dnnweaver import DnnWeaverModel
    model = DnnWeaverModel()
    oracle = reference.Oracle(_cfg("gandse-dnnweaver"))
    net, lo, po = reference.sample_tasks(oracle, 30, seed=3)
    rng = np.random.default_rng(3)
    for t in range(30):
        logits = rng.normal(size=oracle.space.onehot_width)
        probs = np.concatenate([np.exp(g) / np.exp(g).sum() for g in
                                oracle.space.groups(logits)]).astype(np.float32)
        cand = reference.enumerate_candidates(oracle.space, probs, 0.1, 4096)
        want = select(model, net[t], cand.astype(np.int32), lo[t], po[t],
                      use_jax=False)
        got = reference.select(oracle, net[t], probs, 0.1, 4096, lo[t], po[t])
        assert (got[0] is None) == (want.cfg_idx is None)
        if got[0] is not None:
            np.testing.assert_array_equal(got[0], want.cfg_idx)
            assert (got[1], got[2], got[3]) == (want.latency, want.power,
                                                want.satisfied)


def test_g_forward_matches_program():
    from repro.core import gan as G
    cfg = _cfg("gandse-im2col")
    oracle = reference.Oracle(cfg)
    shapes = reference.mlp_shapes(16, 64, 2, oracle.space.onehot_width)
    params = reference.make_init(shapes)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    ne = rng.normal(size=(8, 6)).astype(np.float32)
    oe = rng.normal(size=(8, 2)).astype(np.float32)
    seeds = np.arange(8) + 2**33
    got = reference.make_g_probs(oracle.space, 8)(params, ne, oe, seeds)
    from repro.design_models.im2col import Im2colModel
    from repro.core.explorer import task_keys
    model = Im2colModel()
    keys = task_keys(seeds, 8)
    noise = jax.vmap(lambda k: G.sample_noise(jax.random.fold_in(k, 0), 1,
                                              G.GANConfig(n_net=6))[0])(keys)
    with jax.default_matmul_precision("highest"):
        want = G.generator_apply(params, model.space, ne, oe, noise,
                                 use_fused=False)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-6)
