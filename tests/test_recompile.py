"""Zero-recompile pins on the warm hot paths (tools/lint/recompile_guard).

The PR-2/PR-4 cache-key contract: `pow2_bucket` pads task counts (and the
serve batcher pads micro-batches) so every in-bucket batch size reuses one
jit cache entry.  These tests warm each hot path once, then drive it with
*different* task counts inside the same pow2 bucket and assert the XLA
compile counter does not move.  A failure here means a cache key or the
bucketing broke — the exact regression GL109 (jit-per-call) guards
statically.
"""
import jax
import pytest

from repro.core import gan as G
from repro.core.dse_api import GANDSE
from repro.core.explorer import ExplorerConfig
from repro.dataset.generator import generate_tasks
from repro.design_models.dnnweaver import DnnWeaverModel
from repro.serve import DSEServer, ServeConfig


@pytest.fixture(scope="module")
def engine(tiny_gan_cfg, small_dataset):
    model = DnnWeaverModel()
    cfg = tiny_gan_cfg(model)
    eng = GANDSE(model, cfg,
                 ExplorerConfig(prob_threshold=0.1, max_candidates=128))
    ds = small_dataset(model, n=256)
    eng.attach(ds, G.init_generator(jax.random.PRNGKey(3), cfg, model.space))
    return eng


def test_explore_batch_in_bucket_zero_recompiles(engine, no_recompile):
    """5/6/7-task batches all pad to the pow2 bucket 8: after an 8-task
    warmup, none of them may compile anything new."""
    warm = generate_tasks(engine.model, 8, seed=11)
    engine.explore_batch(warm, seed=101)        # warm bucket 8 end to end
    with no_recompile(label="explore_batch in-bucket"):
        for n, seed in ((5, 202), (6, 303), (7, 404)):
            tasks = generate_tasks(engine.model, n, seed=seed)
            results = engine.explore_batch(tasks, seed=seed)
            assert len(results) == n


def test_fused_route_zero_recompiles_across_candidate_counts(engine,
                                                             no_recompile):
    """The fused tiled program is static in everything but the task
    bucket: threshold and cap are traced arguments and the tile-loop trip
    count is ceil(max(total)/tile) computed on device, so warm dispatches
    with different candidate counts — including caps that span one tile
    vs several — reuse ONE compiled program.  (A route that materialized
    the candidate set could not pass this: its padded size would be a
    static shape picked by a host sync.)
    """
    warm = generate_tasks(engine.model, 8, seed=11)
    engine.explore_batch(warm, seed=101)        # warm bucket 8 at cap 128
    cfg = engine.explorer_cfg
    base = (cfg.prob_threshold, cfg.max_candidates)
    try:
        with no_recompile(label="fused route across candidate counts"):
            counts_seen = set()
            for thresh, cap, n, seed in ((0.30, 32, 5, 17),
                                         (0.05, 64, 6, 23),
                                         (0.02, 256, 7, 29),
                                         (0.01, 2048, 8, 31)):  # multi-tile
                cfg.prob_threshold, cfg.max_candidates = thresh, cap
                tasks = generate_tasks(engine.model, n, seed=seed)
                results = engine.explore_batch(tasks, seed=seed)
                assert len(results) == n
                counts_seen.update(r.selection.n_candidates for r in results)
        # the sweep really produced different candidate-set sizes
        assert len(counts_seen) > 4, counts_seen
    finally:
        cfg.prob_threshold, cfg.max_candidates = base


def test_warm_serve_dispatch_zero_recompiles(engine, no_recompile):
    """Warm `DSEServer` dispatch: micro-batches of 5/6/7 distinct requests
    (cache disabled, so every round really dispatches) pad to bucket 8 and
    must reuse the warmup's compiled path."""
    srv = DSEServer(ServeConfig(max_batch=8, cache_capacity=0))
    srv.register(engine)

    def drive(n, task_seed, req_seed):
        tasks = generate_tasks(engine.model, n, seed=task_seed)
        for i in range(n):
            srv.submit(engine.model.name, tasks.net_idx[i],
                       tasks.lat_obj[i], tasks.pow_obj[i],
                       seed=req_seed + i)
        responses = srv.drain()
        assert len(responses) == n

    batches0 = srv.stats["batches"]
    drive(8, 21, 1000)                          # warm bucket 8
    with no_recompile(label="warm serve dispatch"):
        drive(5, 22, 2000)
        drive(6, 23, 3000)
        drive(7, 24, 4000)
    assert srv.stats["batches"] == batches0 + 4   # all four really dispatched


def test_swap_path_zero_recompiles(tiny_gan_cfg, small_dataset, no_recompile):
    """Hot-swap is parameter-only: after warming a bucket, swapping in new
    generator params (same shapes) and re-dispatching inside the same
    bucket must not compile anything — the online loop swaps once per
    generation, so a retrace here would stall serving every few seconds."""
    model = DnnWeaverModel()
    cfg = tiny_gan_cfg(model)
    eng = GANDSE(model, cfg,
                 ExplorerConfig(prob_threshold=0.1, max_candidates=128))
    ds = small_dataset(model, n=256)
    params_a = G.init_generator(jax.random.PRNGKey(3), cfg, model.space)
    params_b = G.init_generator(jax.random.PRNGKey(9), cfg, model.space)
    eng.attach(ds, params_a)

    srv = DSEServer(ServeConfig(max_batch=8, cache_capacity=0))
    srv.register(eng)

    def drive(n, task_seed, req_seed):
        tasks = generate_tasks(model, n, seed=task_seed)
        for i in range(n):
            srv.submit(model.name, tasks.net_idx[i],
                       tasks.lat_obj[i], tasks.pow_obj[i],
                       seed=req_seed + i)
        assert len(srv.drain()) == n

    drive(8, 31, 1000)              # warm bucket 8 with generation-0 params
    with no_recompile(label="swap + redispatch"):
        srv.swap(model.name, ds, params_b)
        drive(6, 32, 2000)
        srv.swap(model.name, ds, params_a)   # swap back — still warm
        drive(7, 33, 3000)
    assert srv.stats["swaps"] == 2
