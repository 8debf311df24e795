"""DeepSeek-V3 training-layout DSE (``tpu_mesh:DeepSeekV3Mesh``) against
the benchmark's plain reference (``chipbench/reference.py`` and
``chipbench/oracles/tpu_mesh.py``), on the CPU at a small G width.

- the program's float64 oracle equals the reference oracle exactly;
- the preset is the configuration file's, and the file carries the
  published config.json numbers;
- the normal serving path (``GANDSE.explore_tasks``: G forward, fused
  select, float64 host tail) picks what ``itertools.product`` enumeration
  and the sequential Algorithm 2 pick in float64, on the same
  probabilities, in a batch whose winners tie exactly with later
  candidates (the oracle is a max of terms: COMPRESS, for one, moves
  nothing on one replica);
- the fused select's feasible-candidate counter, in ``GANDSE.stats`` and
  on the ``dse.host_tail`` span, and the benchmark's ``feasible_share``
  reader of it;
- the program's samplers draw from a space where about 1% of the layouts
  are feasible, and give up on one where none is.
"""
import os

import jax
import numpy as np
import pytest

from chipbench import harness, reference, tracing
from repro.core import gan as G
from repro.core.dse_api import GANDSE
from repro.core.encoding import ConfigSpace
from repro.core.explorer import ExplorerConfig
from repro.dataset.generator import (DSETask, generate_dataset,
                                     generate_tasks)
from repro.design_models.base import make_dim
from repro.design_models.tpu_mesh import (DEEPSEEK_V3, NET_DIMS,
                                          DeepSeekV3Mesh, TpuMeshModel)
from repro.serve import DSEServer, ServeConfig, ServeFrontend

MODEL = DeepSeekV3Mesh()
CFG = harness.load_json(os.path.join(harness.HERE, "configs",
                                     "gandse-tpu-mesh-dsv3.json"))
CELL = "dsv3-mesh-sweep-full"

#: the preset's dims and the config.json keys that state them
CONFIG_JSON = {
    "LAYERS": "num_hidden_layers", "DENSE": "first_k_dense_replace",
    "MTP": "num_nextn_predict_layers", "DMODEL": "hidden_size",
    "DFF": "intermediate_size", "EXPERTS": "n_routed_experts",
    "TOPK": "num_experts_per_tok", "SHARED": "n_shared_experts",
    "EFF": "moe_intermediate_size", "HEADS": "num_attention_heads",
    "QLORA": "q_lora_rank", "KVLORA": "kv_lora_rank",
    "DNOPE": "qk_nope_head_dim", "DROPE": "qk_rope_head_dim",
    "DV": "v_head_dim", "VOCAB": "vocab_size",
    "TIED": "tie_word_embeddings"}


@pytest.fixture(scope="module")
def oracle():
    return reference.Oracle(CFG)


@pytest.mark.parametrize("space", ["preset", "generic"])
def test_program_oracle_equals_benchmark_reference(oracle, space):
    """At 4,096 points each: the preset's space, and the generic
    descriptor's (dense and sparse, latent and full-rank, tied and
    untied), where the reference takes values straight."""
    rng = np.random.default_rng(16)
    model = MODEL if space == "preset" else TpuMeshModel()
    net = model.net_space.sample_indices(rng, 4096)
    cfg = model.space.sample_indices(rng, 4096)
    want = model.evaluate_indices(net, cfg)
    got = oracle.impl.evaluate(oracle.k, model.net_space.values_from_indices(
        net), model.space.values_from_indices(cfg))
    assert 0 < np.isfinite(want[0]).sum() < 4096
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_preset_is_the_configuration_files():
    from chipbench.serving import check_spaces
    check_spaces(MODEL, CFG)
    for dim, key in CONFIG_JSON.items():
        assert DEEPSEEK_V3[dim] == CFG[key], dim
    assert CFG["net_space"]["WINDOW"] == [max(CFG["net_space"]["SEQ"])]
    assert CFG["design_model"] == "tpu_mesh"
    assert CFG["program_model"].endswith(":" + type(MODEL).__name__)
    assert MODEL.space.size == 235_200 and MODEL.space.max_group_size == 7
    assert CFG["reduced"] == []


@pytest.fixture(scope="module")
def engine(tiny_gan_cfg):
    cfg = tiny_gan_cfg(MODEL, neurons=64)
    g = GANDSE(MODEL, cfg, ExplorerConfig(prob_threshold=0.0,
                                          max_candidates=1 << 18))
    g.attach(generate_dataset(MODEL, 512, seed=0),
             G.init_generator(jax.random.PRNGKey(16), cfg, MODEL.space))
    return g


@pytest.mark.parametrize("thresh,cap", [(0.0, 1 << 18), (0.0, 4096),
                                        (0.06, 1 << 18)],
                         ids=["whole_space", "trimmed", "threshold"])
def test_served_selections_equal_reference(engine, oracle, thresh, cap):
    """Eight tasks through the normal path against the reference on the
    program's own probabilities.  With the whole space scanned the
    winners tie: a later candidate has the winner's exact (latency,
    power), so only first-wins order picks it."""
    net, lo, po = reference.sample_tasks(oracle, 8, seed=11)
    seeds = np.arange(8) + 2 ** 33
    xcfg = engine.explorer_cfg
    old = xcfg.prob_threshold, xcfg.max_candidates
    xcfg.prob_threshold, xcfg.max_candidates = thresh, cap
    s0 = dict(engine.stats)
    try:
        res = engine.explore_tasks(DSETask(net, lo, po), seed=seeds)
    finally:
        xcfg.prob_threshold, xcfg.max_candidates = old
    probs = engine._explorer.generator_probs(net, lo, po, seed=seeds)
    ties = feasible = 0
    for t, r in enumerate(res):
        want = reference.select(oracle, net[t], probs[t], thresh, cap,
                                lo[t], po[t])
        s = r.selection
        assert s.n_candidates == want[4]
        assert (s.cfg_idx is None) == (want[0] is None)
        if want[0] is None:
            continue
        np.testing.assert_array_equal(s.cfg_idx, want[0])
        assert (s.latency, s.power, s.satisfied) == want[1:4]
        cand = reference.enumerate_candidates(oracle.space, probs[t],
                                              thresh, cap)
        lat, pw = oracle(np.broadcast_to(net[t], (len(cand), len(net[t]))),
                         cand)
        feasible += int((np.isfinite(lat) & np.isfinite(pw)).sum())
        ties += int(((lat == s.latency) & (pw == s.power)).sum() > 1)
    if cap == 1 << 18 and thresh == 0.0:
        # every task's witness is among the candidates
        assert all(r.selection.cfg_idx is not None for r in res)
        assert {r.selection.n_candidates for r in res} == {235_200}
        assert ties >= 4
    # the counters: candidates scanned, and those found feasible
    d = {k: engine.stats[k] - s0[k] for k in s0}
    assert d["select_scanned"] == sum(r.selection.n_candidates for r in res)
    assert d["select_feasible"] == feasible
    assert 0 < feasible < d["select_scanned"]


def test_samplers_draw_from_the_sparse_preset():
    ds = generate_dataset(MODEL, 2048, seed=3)
    assert ds.n == 2048 and np.isfinite(ds.latency).all()
    tasks = generate_tasks(MODEL, 1024, seed=4)
    assert len(tasks) == 1024 and np.isfinite(tasks.lat_obj).all()
    # the least feasible phase (128K tokens, 15,360 sequences) holds no
    # feasible layout: the samplers give up instead of looping
    dead = DeepSeekV3Mesh()
    dims = list(dead.net_space.dims)
    dims[NET_DIMS.index("SEQ")] = make_dim("SEQ", (131072,))
    dims[NET_DIMS.index("GBATCH")] = make_dim("GBATCH", (15360,))
    dead.net_space = ConfigSpace(dims=tuple(dims))
    with pytest.raises(ValueError, match="feasible"):
        generate_dataset(dead, 8, seed=0)
    with pytest.raises(ValueError, match="feasible"):
        generate_tasks(dead, 8, seed=0)


def test_feasible_share_read_from_a_served_profile(engine, tmp_path,
                                                   monkeypatch):
    """The benchmark's reader finds the counts on the dse.host_tail spans
    of the run's profile, and gives 100 x feasible / scanned over the
    batches of the traced window: the engine's own counts."""
    monkeypatch.setattr(harness, "RESULTS_DIR", str(tmp_path))
    net, lo, po = reference.sample_tasks(reference.Oracle(CFG), 8, seed=12)
    xcfg = engine.explorer_cfg
    old = xcfg.max_candidates
    xcfg.max_candidates = 4096
    try:
        engine.explore_tasks(DSETask(net, lo, po), seed=5)   # compile
        srv = DSEServer(ServeConfig(max_batch=4))
        srv.register(engine)
        s0 = dict(engine.stats)
        tdir = tmp_path / "trace" / f"{CELL}-1"
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                with ServeFrontend(srv) as fe:
                    futs = [fe.submit(MODEL.name, net[i], lo[i], po[i],
                                      seed=5 + i) for i in range(8)]
                    assert all(f.result(timeout=120).ok for f in futs)
        finally:
            jax.profiler.stop_trace()
    finally:
        xcfg.max_candidates = old
    d = {k: engine.stats[k] - s0[k] for k in s0}
    path = tracing.find_xplane(str(tdir))
    window = tracing.Trace(path).window()
    read = harness.reader("feasible_share")
    got = read({"trace": {"window": window}, "cell": {"name": CELL}})
    assert d["select_scanned"] > 0
    assert got == pytest.approx(100.0 * d["select_feasible"]
                                / d["select_scanned"], rel=1e-12)
    # another window, or no trace: nothing to read
    assert read({"trace": {"window": (0, 1)}, "cell": {"name": CELL}}) is None
    assert read({"trace": None, "cell": {"name": CELL}}) is None


def test_feasible_share_reads_nothing_without_the_counts(tmp_path,
                                                         monkeypatch):
    """A program whose dse.host_tail spans carry no counts (as before
    this counter) reads nothing, and raises nothing."""
    monkeypatch.setattr(harness, "RESULTS_DIR", str(tmp_path))
    tdir = tmp_path / "trace" / f"{CELL}-2"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("dse.host_tail", batch=1):
                pass
    finally:
        jax.profiler.stop_trace()
    window = tracing.Trace(tracing.find_xplane(str(tdir))).window()
    assert harness.reader("feasible_share")(
        {"trace": {"window": window}, "cell": {"name": CELL}}) is None
