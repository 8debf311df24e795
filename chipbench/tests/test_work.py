"""Work counts against hand counts, and Algorithm 1's operations against
XLA's own count of the jnp route's step (which adds the elementwise work
and any recompute, so it may only read higher)."""
import jax
import numpy as np
import pytest

from chipbench import harness, reference, work


def test_dense_hand_counts():
    # (2, 3) @ (3, 4): 48 multiply-adds as ops, 8 bias adds, 8 ReLUs
    assert work.dense_fwd(2, 3, 4) == (64.0, 4 * (6 + 12 + 4 + 8))
    assert work.dense_dx(2, 3, 4) == (56.0, 4 * (16 + 12 + 6))
    assert work.dense_dw(2, 3, 4) == (64.0, 4 * (6 + 16 + 12 + 4))


def test_mlp_forward_and_params():
    shapes = reference.mlp_shapes(16, 8, 2, 5)   # 16->8->8->5
    assert work.n_params(shapes) == 16 * 8 + 8 + 8 * 8 + 8 + 8 * 5 + 5
    flops, nbytes = work.mlp_forward(3, shapes)
    assert flops == 2 * 3 * (16 * 8 + 8 * 8 + 8 * 5) + 2 * 3 * (8 + 8 + 5)
    assert nbytes == 4 * (work.n_params(shapes) + 3 * (16 + 5))


def test_paper_width_step():
    cfg = harness.load_json(f"{harness.HERE}/configs/gandse-im2col.json")
    g = reference.mlp_shapes(16, 2048, 11, 73)
    d = reference.mlp_shapes(6 + 73 + 2, 2048, 11, 2)
    assert cfg["g_neurons"] == 2048
    p_g, p_d = work.n_params(g), work.n_params(d)
    flops = work.alg1_step_flops(1024, g, d)
    # 6 P_G + 8 P_D per row, less the two unneeded first-layer input grads
    assert 0.99 * 1024 * (6 * p_g + 8 * p_d) < flops < \
        1024 * (6 * p_g + 8 * p_d)
    calls = work.alg1_dense_calls(1024, g, d)
    assert sum(work.dense_fwd(*s)[0] + 0 for s in calls["fwd"]) > 0
    mm = sum(2.0 * m * k * n for kind in calls.values() for m, k, n in
             [*kind])
    assert mm == pytest.approx(flops)


def test_alg1_flops_against_xla():
    from repro.core import gan as G
    from repro.core import train as T
    from repro.dataset.generator import generate_dataset
    from repro.design_models.im2col import Im2colModel
    model = Im2colModel()
    cfg = G.GANConfig(n_net=6).scaled(2, 256, batch_size=64)
    cfg = cfg.__class__(**{**cfg.__dict__, "use_fused": False})
    g_opt, d_opt, step = T.make_train_step(model, cfg)
    ds = generate_dataset(model, 64, seed=0)
    batch = T.encode_batch(model, ds, np.arange(64))
    gp = G.init_generator(jax.random.PRNGKey(0), cfg, model.space)
    dp = G.init_discriminator(jax.random.PRNGKey(1), cfg, model.space)
    cost = step.lower(gp, dp, g_opt.init(gp), d_opt.init(dp), batch,
                      jax.random.PRNGKey(2)).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    g = reference.mlp_shapes(16, 256, 2, 73)
    d = reference.mlp_shapes(81, 256, 2, 2)
    mine = work.alg1_step_flops(64, g, d)
    assert mine <= cost["flops"] <= 1.3 * mine


def test_dense_roofline_reader():
    from chipbench import harness
    read = harness.reader("dense_roofline")
    g = reference.mlp_shapes(16, 2048, 11, 73)
    d = reference.mlp_shapes(6 + 73 + 2, 2048, 11, 2)
    peak = harness.load_json(f"{harness.HERE}/peaks.json")["TPU v5 lite"]
    calls = work.alg1_dense_calls(1024, g, d)
    least = sum(work.least_time(*work.KERNELS[k](*s), peak)[0]
                for k, shapes in calls.items() for s in shapes)
    ops = {"%jvp_jit_fused_dense__.1 = f32[1024,2048] custom-call(x)":
           [3 * least, 48],
           "%transpose_jvp_jit_fused_dense___.2 = f32[1024,2048] "
           "custom-call(y)": [3 * least, 116],
           "%jvp_jit_fused_dense__.3 = f32[1024,2048] fusion(z)": [9.0, 2]}
    ctx = {"trace": {"ops": ops, "devices": 1}, "steps": 2, "batch": 1024,
           "g_shapes": g, "d_shapes": d, "peak": peak}
    assert read(ctx) == pytest.approx(100.0 * 2 / 6)
    assert read({**ctx, "trace": {"ops": {}, "devices": 1}}) is None
