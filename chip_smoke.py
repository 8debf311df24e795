"""Smoke run of GANDSE on a TPU through the entry points a user calls.

    python chip_smoke.py              # one chip: train and serve at paper width
    python chip_smoke.py --chips 4    # four chips: the task-mesh path only

One chip (the default), for the im2col and dnnweaver design models, with
`GANConfig` defaults — the paper's Table 4 size, 11 x 2048 G and D, batch
1024 — and random weights from ``--seed``:

- parity: the first Algorithm 1 step (losses and rates) and the G+D
  gradients of one batch on the Pallas kernel route against the jnp route,
  both under ``default_matmul_precision("highest")``, within the
  tolerances below; at the default precision the program runs with, the
  kernel route's error must stay within twice the jnp route's; and both
  routes' "highest" gradients against the same gradients in float64 on
  the host CPU, the reference free of the chip's matmul passes;
- train: ``GANDSE.train`` for a few scanned epochs; every loss finite;
- serve: the trained engine behind a `DSEServer` and the threaded
  `ServeFrontend` (`repro.launch.dse_serve`'s ``--concurrent`` path) with
  the default `ExplorerConfig` and fused select route; every response
  DONE, none from the degraded host route, each Selection equal to a
  standalone ``GANDSE.explore`` call;
- the compiled train epoch and G forward must hold Pallas kernels
  (``tpu_custom_call``).

Four chips (``--chips 4``), on the task mesh (`repro.core.shard`):
sharded ``explore_tasks`` Selections bit-identical to a one-device
submesh run, and one two-step data-parallel ``train_gan`` epoch equal to
one-device training within tests/test_shard.py's tolerance, with the
perm and the carry spread over all four chips; the training comparison
runs once at "highest" and once at the default precision.

Exits nonzero, printing no result, when JAX finds no TPU or any check
fails.  Timings and memory printed on the way are smoke timings of one
run, not benchmark numbers.  The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "src"), HERE]
# the float64 gradient reference runs on the host's CPU backend
if os.environ.get("JAX_PLATFORMS") and \
        "cpu" not in os.environ["JAX_PLATFORMS"].split(","):
    os.environ["JAX_PLATFORMS"] += ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import gan as G  # noqa: E402
from repro.core import shard  # noqa: E402
from repro.core import train as T  # noqa: E402
from repro.core.dse_api import GANDSE  # noqa: E402
from repro.core.explorer import task_seeds  # noqa: E402
from repro.dataset.generator import generate_dataset, generate_tasks  # noqa: E402
from repro.design_models.dnnweaver import DnnWeaverModel  # noqa: E402
from repro.design_models.im2col import Im2colModel  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.dse_serve import (serve_problems, serve_tasks,  # noqa: E402
                                    warm_bucket)
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.serve import DSEServer, ServeConfig  # noqa: E402
from tools.lint.recompile_guard import track_compiles  # noqa: E402

#: first-step parity against the jnp route at "highest": relative error
#: of each loss, relative L2 error of the worst gradient leaf (both for
#: the kernel route at "highest"; at default precision they are the slack
#: over twice the jnp route's own error; the gradient limit also holds
#: against float64), absolute error of each batch rate (a few rows may
#: flip an argmax on a near-tie).  On a v5e the worst leaf (G's first
#: layer w) measured 1.4e-3 between the routes at "highest", each route
#: about 1e-3 from float64, and 0.13 at default precision on both routes;
#: a wrong kernel is off by O(1).
LOSS_RTOL = 1e-4
GRAD_RTOL = 1e-2
RATE_ATOL = 0.02
#: data-parallel vs one-device training (tests/test_shard.py)
TRAIN_RTOL, TRAIN_ATOL, LOSS_HIST_ATOL = 2e-4, 1e-6, 1e-3
#: the smoke's sizes: training rows (8 scanned steps per epoch at batch
#: 1024), epochs, and distinct requests per model (plus repeats)
ROWS, EPOCHS, REQUESTS = 8192, 2, 32

LOSSES = ("loss_g", "loss_d", "loss_config", "loss_critic")
RATES = ("sat_rate", "d_acc")


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    with track_compiles() as rec:
        yield
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] {name}: passed in {time.perf_counter() - t0:.1f} s, "
          f"{rec.count} compiles, device 0 peak "
          f"{stats.get('peak_bytes_in_use', 'n/a')} bytes "
          f"(smoke timing, not a benchmark)", flush=True)


def kernel_text(jitted, *args, **kwargs) -> str:
    """Compiled text of a jitted function, checked for Pallas kernels."""
    text = jitted.lower(*args, **kwargs).compile().as_text()
    check("tpu_custom_call" in text, "no Pallas kernel in the compiled "
          "program: the kernel route was not taken")
    return text


def same_selection(a, b) -> bool:
    """Selections agree: candidate count, chosen config, its host-oracle
    metrics and feasibility."""
    return (a.n_candidates == b.n_candidates
            and (a.cfg_idx is None) == (b.cfg_idx is None)
            and (a.cfg_idx is None or np.array_equal(a.cfg_idx, b.cfg_idx))
            and a.latency == b.latency and a.power == b.power
            and a.satisfied == b.satisfied)


def first_batch(model, cfg, seed: int):
    """One encoded batch and the init params/rng `train_gan` would use."""
    ds = generate_dataset(model, cfg.batch_size, seed=seed)
    batch = {k: jnp.asarray(v) for k, v in
             T.encode_batch(model, ds, np.arange(cfg.batch_size)).items()}
    rng, g_rng, d_rng = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (batch, G.init_generator(g_rng, cfg, model.space),
            G.init_discriminator(d_rng, cfg, model.space), rng)


def worst_leaf(grads, ref) -> tuple:
    """(relative L2 error, path) of the gradient leaf furthest from ref."""
    return max((float(np.linalg.norm(a - b) / np.linalg.norm(b)),
                jax.tree_util.keystr(path))
               for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(
                   grads), jax.tree.leaves(ref)) if np.linalg.norm(b) > 0)


def step_parity(model, cfg, seed: int) -> dict:
    """First Algorithm 1 step and one G+D gradient on the kernel route
    against the jnp route, both at "highest" precision; and, at the
    program's default precision, the kernel route no less accurate than
    the jnp route.  Both routes' "highest" gradients are also measured
    against float64 on the host CPU.  Returns the measured errors."""
    batch, gp, dp, rng = first_batch(model, cfg, seed)
    noise = G.sample_noise(rng, cfg.batch_size, cfg)
    space = model.space

    def gan_loss(gp, dp, batch, noise, use_fused):
        probs = G.generator_apply(gp, space, batch["net_enc"],
                                  batch["obj_enc"], noise, use_fused=use_fused)
        logits = G.discriminator_apply(dp, batch["net_enc"], probs,
                                       batch["obj_enc"], use_fused=use_fused)
        ones = jnp.ones(logits.shape[:1], logits.dtype)
        return (jnp.mean(G.grouped_cross_entropy(space, batch["cfg_onehot"],
                                                 probs))
                + jnp.mean(G.satisfaction_ce(logits, ones)))

    grad = jax.jit(jax.grad(gan_loss, argnums=(0, 1)), static_argnums=4)

    def run(use_fused, precision):
        c = dataclasses.replace(cfg, use_fused=use_fused)
        g_optim, d_optim, step = T.make_train_step(model, c)
        with jax.default_matmul_precision(precision):
            *_, metrics = step(gp, dp, g_optim.init(gp), d_optim.init(dp),
                               batch, rng)
            grads = grad(gp, dp, batch, noise, use_fused)
        return ({k: float(v) for k, v in metrics.items()},
                jax.tree.map(np.asarray, grads))

    # cfg.use_fused (None on a TPU) takes the kernel route; False is jnp
    ref_m, ref_g = run(False, "highest")

    def errors(m, g):
        out = {k: abs(m[k] - ref_m[k]) / max(abs(ref_m[k]), 1e-6)
               for k in LOSSES}
        out.update({k: abs(m[k] - ref_m[k]) for k in RATES})
        out["grad"], out["grad_leaf"] = worst_leaf(g, ref_g)
        check(bool(np.isfinite(list(m.values())).all()), f"non-finite {m}")
        return out

    runs = {f"{route}-{prec}": run(fused, prec)
            for route, fused in (("kernel", cfg.use_fused), ("jnp", False))
            for prec in ("highest", "default")
            if (route, prec) != ("jnp", "highest")}
    err = {name: errors(*r) for name, r in runs.items()}
    print(f"[smoke] parity:{model.name} errors against jnp at highest: "
          f"{json.dumps(err)}", flush=True)

    # float64 on the host: jnp route, same params, batch and noise
    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True):
        f64 = jax.tree.map(lambda a: np.asarray(a, np.float64)
                           if jnp.issubdtype(a.dtype, jnp.floating)
                           else np.asarray(a), (gp, dp, batch, noise))
        g64 = jax.tree.map(np.asarray, grad(*jax.device_put(f64, cpu),
                                            False))
    w0 = lambda g: {"w0": g[0]["layers"][0]["w"]}   # G's first-layer w
    vs64 = {name: worst_leaf(g, g64) + worst_leaf(w0(g), w0(g64))[:1]
            for name, g in (("kernel-highest", runs["kernel-highest"][1]),
                            ("jnp-highest", ref_g))}
    print(f"[smoke] parity:{model.name} gradients at highest against "
          f"float64 on the host (worst leaf, its path, G's first-layer w): "
          f"{json.dumps(vs64)}", flush=True)
    check(vs64["kernel-highest"][0] <= GRAD_RTOL, f"gradient against "
          f"float64: error {vs64['kernel-highest']} > {GRAD_RTOL}")
    err["vs_f64"] = vs64
    hi, kd, jd = err["kernel-highest"], err["kernel-default"], \
        err["jnp-default"]
    for k in LOSSES + ("grad",):
        tol = GRAD_RTOL if k == "grad" else LOSS_RTOL
        check(hi[k] <= tol, f"{k} at highest: error {hi[k]} > {tol}")
        check(kd[k] <= 2 * jd[k] + tol, f"{k} at default precision: kernel "
              f"error {kd[k]} > 2 x jnp error {jd[k]} + {tol}")
    for k in RATES:
        for e in (hi, kd):
            check(e[k] <= RATE_ATOL, f"{k}: error {e[k]} > {RATE_ATOL}")
    return err


def train_engine(model, cfg, seed: int):
    """`GANDSE.train`; checks the losses and the compiled epoch."""
    engine = GANDSE(model, cfg)
    check(dispatch.kernel_route_active(cfg.use_fused),
          "dispatch rule did not pick the kernel route")
    state = engine.train(ROWS, EPOCHS, seed=seed)
    losses = np.array([[h[k] for k in LOSSES] for h in state.history])
    check(losses.shape[0] == EPOCHS * (ROWS // cfg.batch_size),
          f"{losses.shape[0]} steps recorded")
    check(bool(np.isfinite(losses).all()), "non-finite training loss")
    print(f"[smoke] train:{model.name} {losses.shape[0]} steps, loss_g "
          f"{losses[0, 0]:.4f} -> {losses[-1, 0]:.4f}, loss_d "
          f"{losses[0, 1]:.4f} -> {losses[-1, 1]:.4f}", flush=True)

    epoch = T._cached_epoch_fn(model, cfg, None, None)[2]
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    carry = jax.tree.map(shape, (state.g_params, state.d_params, state.g_opt,
                                 state.d_opt, state.rng))
    data = jax.tree.map(shape, T.encode_dataset(model, engine.ds))
    perm = jax.ShapeDtypeStruct((ROWS // cfg.batch_size, cfg.batch_size),
                                np.int32)
    text = kernel_text(epoch, carry, data, perm)
    print(f"[smoke] train:{model.name} compiled epoch holds "
          f"{text.count('tpu_custom_call')} kernel calls", flush=True)
    return engine


def serve_engine(engine, seed: int) -> None:
    """Serve through the concurrent front end; every response DONE on the
    device route and equal to a standalone `explore`."""
    model = engine.model
    srv = DSEServer(ServeConfig(max_batch=16))
    srv.register(engine)
    check(srv.summary()["kernels"]["fused"][model.name],
          "served engine is not on the kernel route")
    tasks = generate_tasks(model, REQUESTS, seed=seed + 2)
    n_rep = REQUESTS // 4
    warm_bucket(srv, model.name, tasks, seed=seed)
    responses, line = serve_tasks(srv, model.name, tasks, seed=seed,
                                  n_rep=n_rep, concurrent=True)
    problems = serve_problems(srv, responses, REQUESTS + 2 * n_rep)
    not_done = [r for r in responses if not r.ok]
    check(not problems and not not_done,
          f"serving: {problems} {len(not_done)} responses not DONE")
    print(f"[smoke] serve:{model.name} {len(responses)} responses DONE, "
          f"batches={srv.stats['batches']} coalesced={srv.stats['coalesced']} "
          f"cache_hits={srv.cache.stats()['hits']} {line}", flush=True)

    for r in responses:
        want = engine.explore(r.net_idx, r.result.lat_obj, r.result.pow_obj,
                              seed=r.seed).selection
        got = r.result.selection
        check(same_selection(got, want), f"served Selection of rid {r.rid} "
              f"differs from explore(): {got} vs {want}")
    n_sat = sum(r.result.selection.satisfied for r in responses)
    print(f"[smoke] serve:{model.name} every Selection equals a standalone "
          f"explore(); {n_sat}/{len(responses)} satisfied", flush=True)

    fwd = engine._explorer._fwd
    t = srv.cfg.max_batch
    ds = engine.ds
    net = ds.net_encoded(model, tasks.net_idx[:t])
    obj = ds.obj_encoded(tasks.lat_obj[:t], tasks.pow_obj[:t])
    kernel_text(fwd, engine.g_params, net, obj, task_seeds(seed, t),
                n_samples=engine.explorer_cfg.noise_samples)
    print(f"[smoke] serve:{model.name} compiled G forward holds the "
          f"megakernel", flush=True)


def paper_cfg(model):
    """`GANConfig` defaults: Table 4, 11 x 2048 G and D, batch 1024."""
    return G.GANConfig(n_net=model.net_space.n_dims)


def one_chip(seed: int, make_cfg=paper_cfg) -> None:
    for model in (Im2colModel(), DnnWeaverModel()):
        cfg = make_cfg(model)
        with phase(f"parity:{model.name}"):
            step_parity(model, cfg, seed)
        with phase(f"train:{model.name}"):
            engine = train_engine(model, cfg, seed)
        with phase(f"serve:{model.name}"):
            serve_engine(engine, seed)
        del engine


def dp_train_parity(model, cfg, ds, mesh, single, seed: int) -> None:
    """One data-parallel `train_gan` epoch on `mesh` against the same epoch
    on one device: parameters within tests/test_shard.py's tolerance, and
    the perm and the carry spread over every chip of the mesh."""
    with shard.task_mesh(single):
        base = T.train_gan(model, ds, cfg, iters=1, seed=seed)
    perms = []
    put_sharded = shard.put_sharded

    def spy(x, mesh=None, axis=0):
        out = put_sharded(x, mesh, axis)
        if axis == 1:
            perms.append(len(out.sharding.device_set))
        return out

    shard.put_sharded = spy
    try:
        with shard.task_mesh(mesh), warnings.catch_warnings():
            # train_gan warns when it drops the mesh
            warnings.simplefilter("error", RuntimeWarning)
            sharded = T.train_gan(model, ds, cfg, iters=1, seed=seed)
    finally:
        shard.put_sharded = put_sharded
    check(perms == [mesh.size], f"epoch perms placed on {perms} devices")
    spans = {len(a.sharding.device_set) for a in jax.tree.leaves(
        (sharded.g_params, sharded.d_params, sharded.g_opt, sharded.d_opt))}
    check(spans == {mesh.size}, f"carry spans {spans} devices")
    steps = [abs(ha["loss_g"] - hb["loss_g"])
             for ha, hb in zip(base.history, sharded.history)]
    bad = []
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(
                (base.g_params, base.d_params)),
            jax.tree.leaves((sharded.g_params, sharded.d_params))):
        a, b = np.asarray(a), np.asarray(b)
        over = np.abs(a - b) > TRAIN_ATOL + TRAIN_RTOL * np.abs(b)
        if over.any():
            bad.append((jax.tree_util.keystr(path), int(over.sum()),
                        a.size, float(np.max(np.abs(a - b)))))
    print(f"[smoke] {len(sharded.history)} data-parallel steps on "
          f"{mesh.size} chips vs one chip: loss_g diff per step {steps}; "
          f"leaves outside rtol {TRAIN_RTOL} atol {TRAIN_ATOL}: {bad}",
          flush=True)
    check(not bad, f"{len(bad)} parameter leaves differ")
    check(max(steps) < LOSS_HIST_ATOL, f"loss_g differs by {max(steps)}")


def four_chips(seed: int, make_cfg=paper_cfg) -> None:
    mesh, single = make_host_mesh(), make_host_mesh((1, 1))
    check(shard.n_task_shards(mesh) == 4, f"task mesh {dict(mesh.shape)}")
    model = Im2colModel()
    cfg = make_cfg(model)
    ds = generate_dataset(model, ROWS, seed=seed)

    with phase("mesh:explore"):
        engine = GANDSE(model, cfg)
        engine.attach(ds, G.init_generator(jax.random.PRNGKey(seed),
                                           cfg, model.space))
        tasks = generate_tasks(model, REQUESTS, seed=seed + 2)
        with shard.task_mesh(single):
            want = engine.explore_tasks(tasks, seed=7)
        with shard.task_mesh(mesh):
            got = engine.explore_tasks(tasks, seed=7)
        for i, (a, b) in enumerate(zip(got, want)):
            check(same_selection(a.selection, b.selection),
                  f"task {i}: sharded {a.selection} vs one device "
                  f"{b.selection}")
        print(f"[smoke] mesh:explore {len(got)} sharded Selections "
              f"bit-identical to the one-device run", flush=True)

    # One epoch of two steps: the sharded gradient sum differs from the
    # one-device sum in its last bits, and Algorithm 1's hard decode and
    # feasibility test are discrete, so within a few more steps some row's
    # argmax near-tie flips and the runs part for good (on a v5e at
    # "highest": loss_g equal to 2e-5 for 6 steps, then 0.02 apart).
    ds = generate_dataset(model, 2 * cfg.batch_size, seed=seed)
    for precision in ("highest", "default"):
        with phase(f"mesh:train:{precision}"), \
                jax.default_matmul_precision(precision):
            dp_train_parity(model, cfg, ds, mesh, single, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the task-mesh path and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    print(f"[smoke] {len(devices)} x {devices[0].device_kind}, "
          f"jax {jax.__version__}", flush=True)
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
