"""Kernel micro-benchmarks: wall time of the XLA execution paths on this
host plus interpret-mode correctness deltas vs the oracles (the TPU perf
story lives in §Roofline — CPU wall times here are only a smoke signal)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import append_trajectory, write_json
from repro.kernels import dispatch
from repro.kernels import fused_mlp as FM
from repro.kernels import ops, ref

TRAJECTORY = "BENCH_kernels.json"


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters


def run() -> dict:
    rng = np.random.default_rng(0)
    out = {}

    x = jnp.asarray(rng.normal(size=(1024, 1024)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1024, 1024)) * 0.03, jnp.float32)
    b = jnp.zeros((1024,), jnp.float32)
    t = _time(lambda a: ops.fused_dense_relu(a, w, b), x)
    err = float(jnp.max(jnp.abs(
        FM.fused_dense(x, w, b, relu=True, interpret=True)
        - ref.fused_dense_relu(x, w, b))))
    out["fused_dense_relu"] = {"us_per_call": t * 1e6, "max_abs_err": err}

    # whole-MLP layer-chained megakernel (3 x 512 hidden): time the actual
    # dispatch path (TPU -> megakernel, CPU -> jnp chain), like the rows
    # above time the ops.* dispatchers
    dims = [(512, 512)] * 3 + [(512, 256)]
    ws = tuple(jnp.asarray(rng.normal(size=d) * 0.05, jnp.float32)
               for d in dims)
    bs = tuple(jnp.zeros((d[1],), jnp.float32) for d in dims)
    layers = [{"w": w, "b": b} for w, b in zip(ws, bs)]
    xm = jnp.asarray(rng.normal(size=(1024, 512)), jnp.float32)
    chain = jax.jit(lambda a: dispatch.mlp_chain(layers, a))
    t = _time(chain, xm)
    err = float(jnp.max(jnp.abs(
        FM.fused_mlp(xm, ws, bs, interpret=True) - ref.fused_mlp(xm, ws, bs))))
    out["fused_mlp_chain"] = {"us_per_call": t * 1e6, "max_abs_err": err}

    q = jnp.asarray(rng.normal(size=(1, 8, 512, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 512, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 512, 64)), jnp.float32)
    t = _time(lambda a: ops.flash_attention(a, k, v), q)
    err = float(jnp.max(jnp.abs(
        ops.flash_attention(q, k, v, interpret=True)
        - ref.flash_attention(q, k, v))))
    out["flash_attention"] = {"us_per_call": t * 1e6, "max_abs_err": err}

    for name, row in out.items():
        print(f"[kernels] {name:18s} {row['us_per_call']:10.1f} us/call "
              f"max_err={row['max_abs_err']:.2e}", flush=True)
    write_json("kernels.json", out)
    append_trajectory(TRAJECTORY, {"bench": "kernels", **out})
    return out


def main():
    run()


if __name__ == "__main__":
    main()
