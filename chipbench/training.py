"""The training cell: Algorithm 1 at the configuration's width through
``repro.core.train.train_gan`` with a warm-started state, ``epochs_per_call``
epochs per call over ``rows`` rows made from the seed.

Set-up builds the one state the window goes on training and drives it
through the window's own call once; the reference follows that first call
step by step afterwards.
"""
from __future__ import annotations

import gc
import importlib
import sys
import time

import numpy as np

from chipbench import harness, reference, tracing
from chipbench.compiles import GcPauses
from chipbench.serving import _span, check_spaces, rng_seed


def leaf_norms(a, b):
    """Per leaf of the two (g, d) parameter trees: ||a - b||, float64."""
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return [float(np.linalg.norm(np.asarray(x, np.float64)
                                 - np.asarray(y, np.float64)))
            for x, y in zip(la, lb)]


def sqrt_sums(tree):
    """Per leaf: sqrt of the sum of Adam's second moment, float64 (a norm of
    the gradients the optimizer has seen, weighted toward the last)."""
    import jax
    return [float(np.sqrt(np.sum(np.asarray(x, np.float64))))
            for x in jax.tree.leaves(tree)]


def leaf_gaps(prog, ref, grad0):
    """Per leaf: |program's norm - reference's| over the larger of the
    reference's norm of that leaf and of the median leaf, as {leaf: gap}.
    Leaves whose first reference gradient is under a thousandth of the
    median leaf's (nought to rounding) are left out."""
    g_med = float(np.median(grad0))
    keep = [i for i, g in enumerate(grad0) if g >= 1e-3 * g_med]
    med = float(np.median([ref[i] for i in keep]))
    return {i: abs(prog[i] - ref[i]) / max(ref[i], med) for i in keep}


class TrainCell:
    def __init__(self, c: dict, seed: int):
        import jax
        from repro.core import gan as G
        from repro.core.encoding import Normalizer, binary_log2_encode
        from repro.dataset.generator import Dataset

        self.c, self.cfg, self.mix = c, c["cfg"], c["mix"]
        cfg, mix = self.cfg, self.mix
        mod, cls = cfg["program_model"].split(":")
        self.model = getattr(importlib.import_module(mod), cls)()
        self.oracle = reference.Oracle(cfg)
        check_spaces(self.model, cfg)
        n_net, width = self.oracle.net_space.n_dims, self.oracle.space.onehot_width
        self.gcfg = G.GANConfig(
            n_net=n_net, n_obj=cfg["n_obj"], noise_dim=cfg["noise_dim"],
            g_hidden_layers=cfg["g_hidden_layers"], g_neurons=cfg["g_neurons"],
            d_hidden_layers=cfg["d_hidden_layers"], d_neurons=cfg["d_neurons"],
            g_lr=cfg["g_lr"], d_lr=cfg["d_lr"], w_critic=cfg["w_critic"],
            batch_size=cfg["batch_size"], dtype=cfg["dtype"])
        self.g_shapes = reference.mlp_shapes(
            n_net + cfg["n_obj"] + cfg["noise_dim"], cfg["g_neurons"],
            cfg["g_hidden_layers"], width)
        self.d_shapes = reference.mlp_shapes(
            n_net + width + cfg["n_obj"], cfg["d_neurons"],
            cfg["d_hidden_layers"], 2)
        self.rows = reference.sample_rows(self.oracle, mix["rows"],
                                          seed=rng_seed(seed, 1))
        net, cf, lat, pw = self.rows
        fit = lambda x: Normalizer.fit(binary_log2_encode(x), center=True)
        self.ds = Dataset(self.model.name, net, cf, lat, pw,
                          lat_norm=fit(lat[:, None]), pow_norm=fit(pw[:, None]),
                          net_norm=fit(self.model.net_space
                                       .values_from_indices(net)))
        self.key = jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)
        self.seed_base = int(np.random.default_rng(rng_seed(seed, 3))
                             .integers(0, 1 << 40))
        g_init = reference.make_init(self.g_shapes)
        d_init = reference.make_init(self.d_shapes)
        self.init = jax.jit(lambda k: (g_init(jax.random.fold_in(k, 0)),
                                       d_init(jax.random.fold_in(k, 1))))

    def call_seed(self, k: int) -> int:
        return self.seed_base + k

    def state0(self):
        import jax
        from repro.core.train import TrainState
        from repro.optim import adam
        g, d = self.init(self.key)
        return TrainState(g, d, adam(self.cfg["g_lr"]).init(g),
                          adam(self.cfg["d_lr"]).init(d),
                          jax.random.fold_in(self.key, 1))

    def call(self, state, k: int):
        from repro.core.train import train_gan
        return train_gan(self.model, self.ds, self.gcfg,
                         iters=self.mix["epochs_per_call"],
                         seed=self.call_seed(k), state=state)

    def first_call(self):
        """Set-up's drive of the state through the window's own call: the
        state, and the program's numbers as ``numbers`` takes them."""
        state = self.call(self.state0(), 0)
        g0, d0 = self.init(self.key)
        prog = ([(h["loss_g"], h["loss_d"]) for h in state.history],
                leaf_norms((state.g_params, state.d_params), (g0, d0)),
                sqrt_sums((state.g_opt.nu, state.d_opt.nu)))
        return state, prog

    def steps_per_call(self) -> int:
        return self.mix["epochs_per_call"] * (self.mix["rows"]
                                              // self.cfg["batch_size"])

    def reference_run(self, control: bool = False, half_batch: bool = False):
        """The reference over the first call's steps: per-step (loss_g,
        loss_d), per-leaf change norms after them, per-leaf ``sqrt_sums`` of
        Adam's second moment after them, and per-leaf norms of the first
        gradient, all in the same leaf order.  ``half_batch`` plants a
        fault: each step sees only the first half of its batch."""
        import jax
        import jax.numpy as jnp
        step, init_state = reference.make_alg1_step(self.cfg, self.oracle,
                                                    control)
        net, cf, lat, pw = self.rows
        enc = reference.Encoder(self.oracle.net_space, net, lat, pw)
        data = {"net_idx": net.astype(np.int32), "net_enc": enc.net_enc(net),
                "cfg_onehot": self.oracle.space.onehot(cf),
                "obj_enc": enc.obj_enc(lat, pw),
                "lat_obj": lat.astype(np.float32),
                "pow_obj": pw.astype(np.float32)}
        g0, d0 = self.init(self.key)
        st, rng = init_state(g0, d0), jax.random.fold_in(self.key, 1)
        losses, grad0 = [], None
        with jax.default_matmul_precision("highest"):
            for perm in reference.epoch_perms(
                    self.mix["rows"], self.cfg["batch_size"],
                    self.call_seed(0), self.mix["epochs_per_call"]):
                for rows in perm:
                    if half_batch:
                        rows = rows[:len(rows) // 2]
                    b = {k: jnp.asarray(v[rows]) for k, v in data.items()}
                    st, rng, (lg, ld), gg = step(st, b, rng)
                    if grad0 is None:
                        grad0 = [float(jnp.linalg.norm(x))
                                 for x in jax.tree.leaves(gg)]
                    losses.append((float(lg), float(ld)))
        change = leaf_norms((st[0], st[1]), (g0, d0))
        return losses, change, sqrt_sums((st[3], st[5])), grad0


def numbers(prog, ref, steps: int):
    """``prog`` and ``ref`` as ``reference_run`` returns them (the program's
    has no first gradient).  loss1_gap: worst relative gap of G's or D's
    loss at the first step; loss_g_gap, loss_d_gap: worst relative gap of
    G's and of D's loss over the first ``steps`` steps; change_gap,
    change_med_gap: worst and median of ``leaf_gaps`` of the parameters'
    change over the first call; grad_gap, grad_med_gap: the same of the
    gradient norms in Adam's second moment after it.  Also returns the
    number of leaves left out and the worst leaf of each."""
    losses, change, nu, grad0 = ref
    loss = lambda j: max(abs(prog[0][t][j] - losses[t][j]) / abs(losses[t][j])
                         for t in range(steps))
    ch, gr = leaf_gaps(prog[1], change, grad0), leaf_gaps(prog[2], nu, grad0)
    worst = lambda d: max(d, key=d.get)
    first = lambda j: abs(prog[0][0][j] - losses[0][j]) / abs(losses[0][j])
    nums = {"loss1_gap": max(first(0), first(1)),
            "loss_g_gap": loss(0), "loss_d_gap": loss(1),
            "change_gap": max(ch.values()),
            "change_med_gap": float(np.median(list(ch.values()))),
            "grad_gap": max(gr.values()),
            "grad_med_gap": float(np.median(list(gr.values())))}
    return nums, {"left_out": len(grad0) - len(ch),
                  "worst_leaf": {"change": worst(ch), "grad": worst(gr)}}


def run(c: dict, args, clock: harness.Clock, counter) -> tuple:
    import jax

    devs = harness.devices(c["chips"])
    peak = harness.peaks(devs[0].device_kind)
    tc = TrainCell(c, args.seed)
    state, prog = tc.first_call()
    trace = bool(args.trace)
    seconds = min(args.seconds, tc.mix["trace_seconds"]) if trace \
        else args.seconds
    tdir = None
    if trace:
        tdir = harness.trace_dir(c["name"], args.seed)
        jax.profiler.start_trace(tdir)
    c0 = counter.count
    setup_s = clock.setup_s()
    pauses = GcPauses()
    calls, k = 0, 1
    with _span("bench.window", trace):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with _span("bench.train_gan", trace):
                state = tc.call(state, k)
            k, calls = k + 1, calls + 1
        t1 = time.perf_counter()
    for k, v in pauses.remove().items():
        print(f"[chipbench] {k} {v}", file=sys.stderr)
    if trace:
        jax.profiler.stop_trace()
    compiles = counter.count - c0
    steps = calls * tc.steps_per_call()
    finite = all(np.isfinite([h["loss_g"] for h in state.history]))
    mem = harness.memory_peak(devs)
    del state
    gc.collect()

    result = {"attempted": steps, "failed": 0 if finite else steps,
              "device": harness.device_info(devs, mem)}
    e2e = {"setup_s": setup_s, "train_step_ms": 1e3 * (t1 - t0) / steps}
    if trace:
        path = tracing.find_xplane(tdir)
        tr = tracing.reduce(path) if path else None
        ctx = {"trace": tr, "peak": peak, "cell": c, "steps": steps,
               "window_s": t1 - t0, "g_shapes": tc.g_shapes,
               "d_shapes": tc.d_shapes, "batch": tc.cfg["batch_size"]}
        result["metrics"] = harness.read_per_layer(c, ctx)
        if tr is not None:
            calls = sum(n for name, (_, n) in tr["ops"].items()
                        if "fused_dense" in name and " custom-call(" in name)
            print(f"[chipbench] fused_dense_calls_per_step "
                  f"{calls / tr['devices'] / steps}", file=sys.stderr)
            result["device"]["busy_s"] = tr["busy_s"]
            result["device"]["window_s"] = tr["window_s"]
            result["breakdown"] = {
                "device_ops": [[n, s / tr["devices"]] for n, s in
                               tracing.top(tr["ops"])],
                "idle_gaps": [list(g) for g in tr["idle_gaps"]]}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in c["end_to_end"]}

    nums, _ = numbers(prog, tc.reference_run(), tc.mix["compare_steps"])
    checks = {k: [nums[k], v] for k, v in tc.mix["limits"].items()}
    checks["window_compiles"] = [compiles, 0]
    checks["failed"] = [result["failed"], 0]
    result["correct"] = all(v <= l for v, l in checks.values())
    return result, checks


def readings(c: dict, seeds, control_seeds, seconds: float, counter):
    """Limit readings: per seed, the program's first call against the
    reference; on ``control_seeds`` also the control (the reference in
    bfloat16) and the half-batch fault (the reference on half of each
    batch), each against the reference."""
    harness.devices(c["chips"])
    steps = c["mix"]["compare_steps"]
    for seed in seeds:
        tc = TrainCell(c, seed)
        state, prog = tc.first_call()
        del state
        ref = tc.reference_run()
        nums, info = numbers(prog, ref, steps)
        row = {"seed": seed, "program": nums, **info,
               "losses_first": ref[0][:steps],
               "losses_prog": prog[0][:steps],
               "change_by_leaf": leaf_gaps(prog[1], ref[1], ref[3]),
               "grad_by_leaf": leaf_gaps(prog[2], ref[2], ref[3]),
               "change_ref": ref[1], "grad0_ref": ref[3]}
        if seed in control_seeds:
            for name, kw in (("control", {"control": True}),
                             ("half_batch", {"half_batch": True})):
                other = tc.reference_run(**kw)
                row[name] = numbers(other, ref, steps)[0]
                row[name + "_losses"] = other[0][:steps]
        yield row
