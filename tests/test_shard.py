"""Task-mesh sharding parity and the three bugfixes riding this PR.

Single-device-safe tests (always run):
- `train/shardings` regression: meshes without a 'pod'/'data' axis (or
  holding them at size 1) must never emit `PartitionSpec((), ...)` or
  reference axes the mesh lacks;
- `make_host_mesh` shape/axes override and its validation errors;
- `MicroBatcher` queue pruning, targeted-pop rotation, and shard-multiple
  sizing.

Multi-device tests (skipped below 4 devices — run them on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``): every batched
DSE route (GANDSE, SA, DRL, LargeMLP) returns bit-identical Selections
under an active task mesh, including ragged task counts; Algorithm 1
training matches single-device up to float reduction order; and the
serving stack end-to-end dispatches shard-multiple batches with
unchanged responses.
"""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import gan as G
from repro.core import shard
from repro.core.dse_api import GANDSE
from repro.core.explorer import ExplorerConfig
from repro.dataset.generator import DSETask, generate_tasks
from repro.design_models.im2col import Im2colModel
from repro.launch.mesh import make_host_mesh
from repro.serve.batcher import MicroBatcher
from repro.serve.request import DSERequest
from repro.train import shardings as SH

N_DEV = 4
multidevice = pytest.mark.skipif(
    len(jax.devices()) < N_DEV,
    reason=f"needs >= {N_DEV} devices; run with "
           f"XLA_FLAGS=--xla_force_host_platform_device_count={N_DEV}")


@pytest.fixture(scope="module")
def model():
    return Im2colModel()


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < N_DEV:
        pytest.skip("multi-device only")
    return make_host_mesh()


def _attached(model, tiny_gan_cfg, small_dataset, seed=3):
    cfg = tiny_gan_cfg(model)
    g = GANDSE(model, cfg,
               ExplorerConfig(prob_threshold=0.1, max_candidates=128))
    ds = small_dataset(model, n=256)
    g.attach(ds, G.init_generator(jax.random.PRNGKey(seed), cfg, model.space))
    return g


def _assert_results_equal(tag, a, b):
    assert len(a) == len(b), tag
    for i, (ra, rb) in enumerate(zip(a, b)):
        sa, sb = ra.selection, rb.selection
        assert sa.n_candidates == sb.n_candidates, (tag, i)
        assert (sa.cfg_idx is None) == (sb.cfg_idx is None), (tag, i)
        if sa.cfg_idx is not None:
            np.testing.assert_array_equal(sa.cfg_idx, sb.cfg_idx,
                                          err_msg=f"{tag}[{i}]")
        assert sa.latency == sb.latency and sa.power == sb.power, (tag, i)
        assert sa.satisfied == sb.satisfied, (tag, i)


# ---------------------------------------------------------------------------
# bugfix regressions (single-device safe)
# ---------------------------------------------------------------------------
def _spec_axes(spec):
    """Flatten every axis name a PartitionSpec mentions."""
    out = []
    for entry in spec:
        if entry is None:
            continue
        assert entry != (), f"PartitionSpec holds an empty tuple: {spec}"
        out.extend(entry if isinstance(entry, tuple) else (entry,))
    return out


def test_specs_on_model_only_mesh_never_name_absent_axes():
    """Regression: a mesh without 'pod'/'data' used to get
    ``P((), ...)`` (empty batch-axes tuple leaking through ``_div``'s
    vacuous size-1 fallback) and specs naming the absent 'data' axis."""

    class ModelOnlyMesh:
        shape = {"model": 8}
        devices = np.empty((8,), object)

    mesh = ModelOnlyMesh()
    act = SH.activation_spec(mesh, batch=32, d_model=512)
    assert act == P(None, None, "model"), act
    st = SH.state_spec((4, 32, 4096, 8, 64), mesh, batch=32)
    for ax in _spec_axes(act) + _spec_axes(st):
        assert ax in mesh.shape, (act, st)


def test_specs_drop_size1_mesh_axes():
    """A size-1 'data' axis (the default 1-device host mesh) shards
    nothing: specs must replicate rather than name it."""

    class OneDeviceMesh:
        shape = {"data": 1, "model": 1}
        devices = np.empty((1, 1), object)

    act = SH.activation_spec(OneDeviceMesh(), batch=32, d_model=512)
    assert act == P(None, None, None), act
    assert SH.norm_axes(("pod", "data"), OneDeviceMesh()) is None
    assert SH.norm_axes((), None) is None
    assert SH.norm_axes("data", None) == ("data",)


def test_specs_on_full_mesh_unchanged():
    """The fix must not perturb specs on a real pod/data/model mesh."""

    class FullMesh:
        shape = {"pod": 2, "data": 16, "model": 16}
        devices = np.empty((2, 16, 16), object)

    act = SH.activation_spec(FullMesh(), batch=64, d_model=4096)
    assert act == P(("pod", "data"), None, "model"), act


def test_make_host_mesh_override_and_submesh():
    n = len(jax.devices())
    default = make_host_mesh()
    assert dict(default.shape) == {"data": n, "model": 1}
    # explicit full shape
    full = make_host_mesh(shape=(n, 1))
    assert dict(full.shape) == {"data": n, "model": 1}
    # submesh over the first device (always possible)
    sub = make_host_mesh(shape=(1, 1))
    assert dict(sub.shape) == {"data": 1, "model": 1}
    assert sub.devices.flatten()[0] == jax.devices()[0]
    # custom axes ride along
    named = make_host_mesh(shape=(1,), axes=("tasks",))
    assert dict(named.shape) == {"tasks": 1}


def test_make_host_mesh_rejects_oversized_shape():
    n = len(jax.devices())
    with pytest.raises(ValueError) as e:
        make_host_mesh(shape=(n + 1, 2))
    # the error names both the requested and the available device count
    assert str(2 * (n + 1)) in str(e.value) and str(n) in str(e.value)
    with pytest.raises(ValueError):
        make_host_mesh(shape=(1, 1), axes=("data",))   # len mismatch
    with pytest.raises(AssertionError):
        make_host_mesh(axes=("data", "model"))         # axes without shape


def _req(rid, model_name="m", seed=None):
    return DSERequest(rid=rid, model_name=model_name,
                      net_idx=np.zeros(3, np.int64),
                      lat_obj=1.0, pow_obj=1.0,
                      seed=rid if seed is None else seed)


def test_batcher_prunes_drained_queues():
    """Regression: `_queues` grew one dead entry per retired model name
    forever (`models_with_work` scanned them on every dispatch)."""
    mb = MicroBatcher(max_batch=64)
    for m in range(20):
        mb.admit(_req(m, model_name=f"model-{m}"))
    for _ in range(20):
        assert mb.next_batch() is not None
    assert mb.next_batch() is None
    assert len(mb._queues) == 0          # drained queues pruned
    assert mb.models_with_work() == []
    # targeted pops prune too
    mb.admit(_req(99, model_name="m"))
    assert mb.next_batch("m") is not None
    assert len(mb._queues) == 0


def test_batcher_targeted_pop_keeps_round_robin_order():
    """Regression: a targeted ``next_batch(model_name=...)`` rotated the
    round-robin order, costing the front model its turn."""
    mb = MicroBatcher(max_batch=1, pad_pow2=False)
    for rid, m in [(0, "a"), (1, "a"), (2, "b"), (3, "b")]:
        mb.admit(_req(rid, model_name=m))
    # targeted pop of the front model must NOT rotate it to the back
    assert mb.next_batch("a").requests[0].rid == 0
    assert mb.models_with_work() == ["a", "b"]
    # round-robin pop serves 'a' (still front), THEN rotates it back
    assert mb.next_batch().requests[0].rid == 1
    assert mb.models_with_work() == ["b"]
    assert mb.next_batch().requests[0].rid == 2


def test_batcher_pads_to_shard_multiple():
    # 5 requests on 4 shards: ceil(5/4)=2 rows/shard, pow2(2)=2 -> 8 rows
    mb = MicroBatcher(max_batch=64, n_shards=4)
    for rid in range(5):
        mb.admit(_req(rid))
    b = mb.next_batch()
    assert (b.n_real, b.padded_size) == (5, 8)
    np.testing.assert_array_equal(b.seeds, [0, 1, 2, 3, 4, 4, 4, 4])
    assert len(b.tasks) == 8
    # without pow2 bucketing the target is the bare shard multiple
    mb = MicroBatcher(max_batch=64, pad_pow2=False, n_shards=4)
    for rid in range(5):
        mb.admit(_req(rid))
    assert mb.next_batch().padded_size == 8
    mb = MicroBatcher(max_batch=64, pad_pow2=False, n_shards=4)
    for rid in range(3):
        mb.admit(_req(rid))
    assert mb.next_batch().padded_size == 4
    # n_shards=1 reproduces the pre-mesh sizing exactly
    mb = MicroBatcher(max_batch=64, n_shards=1)
    for rid in range(5):
        mb.admit(_req(rid))
    assert mb.next_batch().padded_size == 8
    mb = MicroBatcher(max_batch=64, pad_pow2=False, n_shards=1)
    for rid in range(5):
        mb.admit(_req(rid))
    assert mb.next_batch().padded_size == 5


def test_batcher_follows_active_task_mesh():
    """n_shards=None reads the active mesh at formation time."""

    class FakeMesh:
        shape = {"data": 4, "model": 1}
        devices = np.empty((4, 1), object)

    mb = MicroBatcher(max_batch=64)
    for rid in range(3):
        mb.admit(_req(rid))
    with shard.task_mesh(FakeMesh()):
        assert mb.next_batch().padded_size == 4
    for rid in range(3):
        mb.admit(_req(rid + 10))
    assert mb.next_batch().padded_size == 4  # no mesh: plain pow2


def test_pad_tasks_roundtrip():
    class FakeMesh:
        shape = {"data": 4, "model": 1}
        devices = np.empty((4, 1), object)

    tasks = DSETask(net_idx=np.arange(12).reshape(6, 2),
                    lat_obj=np.arange(6.0), pow_obj=np.arange(6.0) + 10)
    seeds = np.arange(6, dtype=np.int64) + 100
    t_p, s_p, n = shard.pad_tasks(tasks, seeds, mesh=FakeMesh())
    assert n == 6 and len(t_p) == 8
    np.testing.assert_array_equal(t_p.net_idx[:6], tasks.net_idx)
    np.testing.assert_array_equal(t_p.net_idx[6:], tasks.net_idx[[5, 5]])
    np.testing.assert_array_equal(s_p, list(range(100, 106)) + [105, 105])
    # no mesh: plain pow2 bucket (same rule as the micro-batcher), so
    # direct explore_batch calls share one jit cache entry per bucket
    t_id, s_id, n_id = shard.pad_tasks(tasks, seeds, mesh=None)
    assert n_id == 6 and len(t_id) == 8
    np.testing.assert_array_equal(t_id.net_idx[:6], tasks.net_idx)
    np.testing.assert_array_equal(t_id.net_idx[6:], tasks.net_idx[[5, 5]])
    # an aligned batch is untouched
    t8, s8, n8 = shard.pad_tasks(t_id, s_id, mesh=None)
    assert t8 is t_id and n8 == 8


# ---------------------------------------------------------------------------
# sharded == single-device parity (multi-device only)
# ---------------------------------------------------------------------------
@multidevice
@pytest.mark.parametrize("n_tasks", [8, 6])   # aligned and ragged on 4
def test_explore_batch_parity_sharded(model, mesh, tiny_gan_cfg,
                                      small_dataset, n_tasks):
    eng = _attached(model, tiny_gan_cfg, small_dataset)
    tasks = generate_tasks(model, n_tasks, seed=2)
    base = eng.explore_batch(tasks, seed=7)
    with shard.task_mesh(mesh):
        sharded = eng.explore_batch(tasks, seed=7)
    _assert_results_equal(f"gandse[{n_tasks}]", base, sharded)


@multidevice
def test_baseline_parity_sharded(model, mesh, small_dataset):
    from repro.baselines.drl import PolicyGradientDRL
    from repro.baselines.mlp import LargeMLP
    from repro.baselines.sa import SimulatedAnnealing

    ds = small_dataset(model, n=256)
    tasks = generate_tasks(model, 6, seed=3)   # ragged on 4 shards
    sa = SimulatedAnnealing(model, cooling=0.6)   # short anneal
    drl = PolicyGradientDRL(model, hidden_layers=2, neurons=16,
                            rollout_len=4).attach(ds, None)
    drl.params = drl.init_params(0)
    lm = LargeMLP(model, hidden_layers=2, neurons=32).attach(ds, None)
    lm.params = lm.init_params(0)
    for eng in (sa, drl, lm):
        base = eng.explore_tasks(tasks, seed=5)
        with shard.task_mesh(mesh):
            sharded = eng.explore_tasks(tasks, seed=5)
        _assert_results_equal(eng.method_name, base, sharded)


@multidevice
def test_train_parity_sharded(model, mesh, tiny_gan_cfg, small_dataset):
    """Data-parallel Algorithm 1 matches single-device up to float
    reduction order (losses are batch means, GSPMD all-reduces grads)."""
    from repro.core.train import train_gan
    cfg = tiny_gan_cfg(model, batch_size=32)
    ds = small_dataset(model, n=128)
    base = train_gan(model, ds, cfg, iters=2, seed=0)
    with shard.task_mesh(mesh):
        sharded = train_gan(model, ds, cfg, iters=2, seed=0)
    for which, pa, pb in (("g", base.g_params, sharded.g_params),
                          ("d", base.d_params, sharded.d_params)):
        flat_a = jax.tree.leaves(pa)
        flat_b = jax.tree.leaves(pb)
        for la, lb in zip(flat_a, flat_b):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=2e-4, atol=1e-6,
                                       err_msg=which)
    # loss history agrees too
    for ha, hb in zip(base.history, sharded.history):
        assert abs(ha["loss_g"] - hb["loss_g"]) < 1e-3, (ha, hb)


@multidevice
def test_kernel_route_parity_sharded(model, mesh, tiny_gan_cfg,
                                     small_dataset):
    """The Pallas route under a task mesh (each kernel call wrapped in
    shard_map, as a multi-chip program requires) == single device, for
    data-parallel training and sharded exploration.  Interpret mode here;
    tests/test_tpu_compile.py compiles the same programs for four chips."""
    import dataclasses

    from repro.core.train import train_gan
    from repro.kernels import dispatch as D

    cfg = dataclasses.replace(tiny_gan_cfg(model, batch_size=32),
                              use_fused=True)
    ds = small_dataset(model, n=128)
    tasks = generate_tasks(model, 6, seed=2)
    with D.force_interpret():
        base = train_gan(model, ds, cfg, iters=1, seed=0)
        with shard.task_mesh(mesh):
            sharded = train_gan(model, ds, cfg, iters=1, seed=0)
        eng = GANDSE(model, cfg,
                     ExplorerConfig(prob_threshold=0.1, max_candidates=128))
        eng.attach(ds, base.g_params)
        single = eng.explore_batch(tasks, seed=7)
        with shard.task_mesh(mesh):
            multi = eng.explore_batch(tasks, seed=7)
    for la, lb in zip(jax.tree.leaves(base.g_params),
                      jax.tree.leaves(sharded.g_params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=2e-4, atol=1e-6)
    _assert_results_equal("kernel route", single, multi)


@multidevice
def test_train_falls_back_when_batch_does_not_divide(model, mesh,
                                                     tiny_gan_cfg,
                                                     small_dataset):
    from repro.core.train import train_gan
    cfg = tiny_gan_cfg(model, batch_size=30)   # 30 % 4 != 0
    ds = small_dataset(model, n=128)
    base = train_gan(model, ds, cfg, iters=1, seed=0)
    with shard.task_mesh(mesh), pytest.warns(RuntimeWarning,
                                             match="training unsharded"):
        sharded = train_gan(model, ds, cfg, iters=1, seed=0)
    la, lb = jax.tree.leaves(base.g_params), jax.tree.leaves(sharded.g_params)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@multidevice
def test_server_end_to_end_under_mesh(model, mesh, tiny_gan_cfg,
                                      small_dataset):
    """Single submissions through the serving stack under an active mesh:
    responses identical to the no-mesh server, batches sized to the shard
    multiple."""
    from repro.serve import DSEServer, ServeConfig

    tasks = generate_tasks(model, 5, seed=6)

    def run(active_mesh):
        srv = DSEServer(ServeConfig(max_batch=64, cache_capacity=0))
        srv.register(_attached(model, tiny_gan_cfg, small_dataset))
        with shard.task_mesh(active_mesh):
            rids = [srv.submit(model.name, tasks.net_idx[i],
                               tasks.lat_obj[i], tasks.pow_obj[i],
                               seed=100 + i)
                    for i in range(5)]
            srv.drain()
        return srv, [srv.response(r) for r in rids]

    srv0, base = run(None)
    srv1, sharded = run(mesh)
    for i, (ra, rb) in enumerate(zip(base, sharded)):
        np.testing.assert_array_equal(ra.result.selection.cfg_idx,
                                      rb.result.selection.cfg_idx,
                                      err_msg=f"serve[{i}]")
        assert ra.result.selection.latency == rb.result.selection.latency
    # 5 requests -> one 8-row batch under the 4-way mesh (3 padded rows)
    assert srv1.stats["padded_rows"] == 3
    assert srv1.summary()["sharding"]["n_shards"] == 1  # mesh exited
    with shard.task_mesh(mesh):
        assert srv1.summary()["sharding"]["n_shards"] == shard.n_task_shards(mesh)
