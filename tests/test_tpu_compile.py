"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode (tests/test_kernels.py, tests/test_fused_path.py) checks
what the kernels compute, not whether the chip's compiler accepts them:
block shapes that break the TPU tiling rules, 1-D layouts and VMEM
overruns are only refused by Mosaic.  These tests hand the kernels — and
the whole paper-width Algorithm 1 train step and G forward — to the TPU
compiler for a chip that is described, not attached, at the paper's
Table 4 size (11 x 2048 G and D, batch 1024), and the data-parallel train
epoch and task-sharded G forward for a four-chip host, where XLA refuses
any kernel call not wrapped in shard_map.  Nothing runs; a compile that
passes is not a chip run.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library at a time, and a test file that
loaded it while being collected would break multi-worker runs.  The
dispatch rule reads the backend at trace time, so the tests steer it onto
the kernel route by patching ``dispatch.on_tpu``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import gan as G
from repro.core import train as T
from repro.core.explorer import _cached_fwd
from repro.core.fused_select import _fused_batch
from repro.design_models.dnnweaver import DnnWeaverModel
from repro.design_models.im2col import Im2colModel
from repro.design_models.tpu_mesh import DeepSeekV3Mesh
from repro.kernels import dispatch as D
from repro.kernels import fused_mlp as FM

from _hlo import loop_ops

MODELS = {"dnnweaver": DnnWeaverModel, "im2col": Im2colModel}
#: the served design models: the benchmark's, DeepSeek-V3's mesh preset too
SERVED = {**MODELS, "tpu_mesh_dsv3": DeepSeekV3Mesh}
HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The task mesh `make_host_mesh()` builds on a 2x2 host."""
    return Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))


@pytest.fixture
def kernel_route(monkeypatch):
    """Make the dispatch rule take the Pallas route while tracing here."""
    monkeypatch.setattr(D, "on_tpu", lambda: True)


def _spec(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    return text


def _paper_cfg(model):
    return G.GANConfig(n_net=model.net_space.n_dims, use_fused=True)


def _gan_shapes(model, cfg):
    rng = jax.random.PRNGKey(0)
    gp = jax.eval_shape(lambda: G.init_generator(rng, cfg, model.space))
    dp = jax.eval_shape(lambda: G.init_discriminator(rng, cfg, model.space))
    return gp, dp, jax.eval_shape(lambda: rng)


def _batch_shapes(model, cfg, n):
    """The encoded training rows `train.encode_batch` produces."""
    f32 = jnp.float32
    return {
        "net_idx": jax.ShapeDtypeStruct((n, cfg.n_net), jnp.int32),
        "net_enc": jax.ShapeDtypeStruct((n, cfg.n_net), f32),
        "cfg_onehot": jax.ShapeDtypeStruct((n, model.space.onehot_width), f32),
        "obj_enc": jax.ShapeDtypeStruct((n, cfg.n_obj), f32),
        "lat_obj": jax.ShapeDtypeStruct((n,), f32),
        "pow_obj": jax.ShapeDtypeStruct((n,), f32),
    }


@pytest.mark.parametrize("which", ["forward", "grad"])
def test_fused_dense_compiles_at_paper_width(one_chip, which):
    m, k, n = 1024, 2048, 2048
    args = _spec(one_chip, (jax.ShapeDtypeStruct((m, k), jnp.float32),
                            jax.ShapeDtypeStruct((k, n), jnp.float32),
                            jax.ShapeDtypeStruct((n,), jnp.float32)))
    fn = FM.fused_dense
    if which == "grad":
        fn = jax.grad(lambda x, w, b: jnp.sum(FM.fused_dense(x, w, b)),
                      argnums=(0, 1, 2))
    text = _compile(fn, *args)
    # forward: one kernel; grad: forward + dx + (dw, db)
    assert text.count("tpu_custom_call") >= (1 if which == "forward" else 3)


@pytest.mark.parametrize("model_name", sorted(MODELS))
@pytest.mark.parametrize("rows", [16, 1024])
def test_fused_mlp_compiles_at_generator_shapes(one_chip, model_name, rows):
    """The megakernel over G's 11 x 2048 stack, from a serving micro-batch
    to a training batch.  At 2048 wide it needs 24-28 MiB of scoped VMEM
    (double-buffered x and weight blocks plus the ping-pong buffers)."""
    model = MODELS[model_name]()
    cfg = _paper_cfg(model)
    params = jax.eval_shape(lambda: G.init_generator(
        jax.random.PRNGKey(0), cfg, model.space))
    ws = tuple(p["w"] for p in params["layers"])
    bs = tuple(p["b"] for p in params["layers"])
    assert len(ws) == 12 and ws[1].shape == (2048, 2048)
    x = jax.ShapeDtypeStruct((rows, ws[0].shape[0]), jnp.float32)
    _compile(FM.fused_mlp, *_spec(one_chip, (x, ws, bs)))


@pytest.mark.parametrize("model_name", sorted(SERVED))
def test_generator_forward_compiles_on_kernel_route(one_chip, kernel_route,
                                                    model_name):
    """The explorer's G forward (what serving dispatches) takes the
    megakernel once the dispatch rule sees a TPU."""
    model = SERVED[model_name]()
    cfg = _paper_cfg(model)
    # a fresh jit, not the process-wide cached one: this trace is routed
    # for a chip and must never serve a CPU call
    fwd = _cached_fwd.__wrapped__(model.space, cfg)
    params, _, _ = _gan_shapes(model, cfg)
    t = 64
    args = _spec(one_chip, (
        params,
        jax.ShapeDtypeStruct((t, cfg.n_net), jnp.float32),
        jax.ShapeDtypeStruct((t, cfg.n_obj), jnp.float32),
        jax.ShapeDtypeStruct((t,), jnp.uint32)))
    _compile(lambda *a: fwd(*a, n_samples=1), *args)


@pytest.mark.parametrize("model_name", sorted(MODELS))
def test_train_step_compiles_at_paper_width(one_chip, kernel_route,
                                            model_name):
    """One Algorithm 1 step at Table 4 size: G and D forward and custom-vjp
    backward all through fused_dense."""
    model = MODELS[model_name]()
    cfg = _paper_cfg(model)
    g_optim, d_optim, step = T.make_train_step(model, cfg)
    gp, dp, rng = _gan_shapes(model, cfg)
    args = _spec(one_chip, (gp, dp, jax.eval_shape(g_optim.init, gp),
                            jax.eval_shape(d_optim.init, dp),
                            _batch_shapes(model, cfg, cfg.batch_size), rng))
    text = _compile(step, *args)
    # 24 layers (G 12 + D 12), D applied twice: every forward and backward
    # layer is a kernel
    assert text.count("tpu_custom_call") >= 3 * 24


def test_sharded_train_epoch_compiles_on_four_chips(four_chips, kernel_route):
    """`train_gan`'s data-parallel epoch over the task mesh: replicated
    carry, perm sharded on its batch axis, every kernel under shard_map,
    gradients all-reduced."""
    model = Im2colModel()
    cfg = _paper_cfg(model)
    g_optim, d_optim, epoch = T.make_epoch_fn(model, cfg, mesh=four_chips)
    gp, dp, rng = _gan_shapes(model, cfg)
    carry = (gp, dp, jax.eval_shape(g_optim.init, gp),
             jax.eval_shape(d_optim.init, dp), rng)
    data = _batch_shapes(model, cfg, 8 * cfg.batch_size)
    replicated = NamedSharding(four_chips, P())
    perm = jax.ShapeDtypeStruct((8, cfg.batch_size), jnp.int32,
                                sharding=NamedSharding(four_chips,
                                                       P(None, "data")))
    text = _compile(epoch, _spec(replicated, carry), _spec(replicated, data),
                    perm)
    assert "all-reduce" in text


def test_sharded_generator_forward_compiles_on_four_chips(four_chips,
                                                          kernel_route):
    model = Im2colModel()
    cfg = _paper_cfg(model)
    fwd = _cached_fwd.__wrapped__(model.space, cfg, mesh=four_chips)
    gp, _, _ = _gan_shapes(model, cfg)
    t = 64
    rows = NamedSharding(four_chips, P("data"))
    _compile(lambda *a: fwd(*a, n_samples=1),
             _spec(NamedSharding(four_chips, P()), gp),
             jax.ShapeDtypeStruct((t, cfg.n_net), jnp.float32, sharding=rows),
             jax.ShapeDtypeStruct((t, cfg.n_obj), jnp.float32, sharding=rows),
             jax.ShapeDtypeStruct((t,), jnp.uint32, sharding=rows))


@pytest.mark.parametrize("model_name", sorted(SERVED))
def test_fused_select_tile_loop_compiles_gather_and_divide_free(one_chip,
                                                                model_name):
    """The serving select at the sweep's batch (T = 64, tile = 1024): its
    tile loop and replay branch hold no candidate-sized gather, and no
    integer division, which the TPU compiler would otherwise sink back
    into the loop from the once-per-call offset decode."""
    model = SERVED[model_name]()
    t, tile = 64, 1024
    f32, i32 = jnp.float32, jnp.int32
    args = _spec(one_chip, (
        jax.ShapeDtypeStruct((t, model.space.onehot_width), f32),
        jax.ShapeDtypeStruct((), f32), jax.ShapeDtypeStruct((), i32),
        jax.ShapeDtypeStruct((t, model.net_space.n_dims), i32),
        jax.ShapeDtypeStruct((t,), f32), jax.ShapeDtypeStruct((t,), f32)))
    text = _fused_batch(model, model.space, tile).lower(*args).compile() \
        .as_text()                     # no Pallas kernel: not `_compile`
    for op in ("gather", "divide", "remainder"):
        big = [(dt, n) for dt, n in loop_ops(text, op)
               if n >= t * tile and (op == "gather" or dt == "s32")]
        assert big == [], (op, big)
