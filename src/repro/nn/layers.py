"""Minimal pure-JAX module substrate (no flax).

Params are explicit pytrees (nested dicts of jnp arrays).  Every layer is a
pair of functions: ``init(rng, ...) -> params`` and ``apply(params, x, ...)``.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Dense / MLP
# ---------------------------------------------------------------------------
def dense_init(rng, in_dim: int, out_dim: int, dtype=jnp.float32, scale: Optional[float] = None):
    w_rng, _ = jax.random.split(rng)
    s = scale if scale is not None else (2.0 / in_dim) ** 0.5  # He init (ReLU nets)
    return {
        "w": (jax.random.normal(w_rng, (in_dim, out_dim), jnp.float32) * s).astype(dtype),
        "b": jnp.zeros((out_dim,), dtype),
    }


def dense_apply(params, x):
    return x @ params["w"] + params["b"]


def mlp_init(rng, in_dim: int, hidden: Sequence[int], out_dim: int, dtype=jnp.float32):
    dims = [in_dim, *hidden, out_dim]
    rngs = jax.random.split(rng, len(dims) - 1)
    layers = []
    for i, r in enumerate(rngs):
        last = i == len(dims) - 2
        scale = (1.0 / dims[i]) ** 0.5 if last else None
        layers.append(dense_init(r, dims[i], dims[i + 1], dtype, scale=scale))
    return {"layers": layers}


def mlp_apply(params, x, activation=jax.nn.relu,
              use_fused: Optional[bool] = None, mesh=None):
    """Plain MLP: hidden layers with `activation`, linear final layer.

    ``use_fused`` routes every layer through the Pallas fused
    dense+bias+ReLU kernels (kernels/fused_mlp.py, differentiable via
    their custom_vjp): ``None`` = backend auto (TPU on, CPU/GPU off —
    see kernels/dispatch.py), ``True``/``False`` force it.  The fused
    kernels hard-wire ReLU, so a non-ReLU ``activation`` raises when
    fusion was explicitly requested and silently takes the unfused path
    on auto (it is never ignored).  ``mesh``: the devices the calling
    program spans, for the kernel route (see kernels/dispatch.py).
    """
    layers = params["layers"]
    from repro.kernels import dispatch as D
    if activation is not jax.nn.relu:
        if use_fused:
            raise ValueError(
                "mlp_apply(use_fused=True) supports only jax.nn.relu — the "
                f"fused kernel hard-wires the ReLU epilogue; got {activation!r}. "
                "Pass use_fused=None/False to use the unfused path.")
        # non-ReLU: always the unfused path, interpret hook included — there is
        # no kernel for this activation, so it is honored, never replaced
    elif D.kernel_route_active(use_fused):
        for p in layers[:-1]:
            x = D.dense(x, p["w"], p["b"], relu=True, use_fused=use_fused,
                        mesh=mesh)
        return D.dense(x, layers[-1]["w"], layers[-1]["b"], relu=False,
                       use_fused=use_fused, mesh=mesh)
    for p in layers[:-1]:
        x = activation(dense_apply(p, x))
    return dense_apply(layers[-1], x)


def mlp_apply_chained(params, x, use_fused: Optional[bool] = None,
                      mesh=None):
    """Inference-only MLP forward (hidden ReLU, linear head) through the
    layer-chained megakernel on the fused route: activations stay in VMEM
    across layers instead of one HBM round-trip per layer.  Differentiable
    too (the megakernel's VJP re-runs the fused_dense chain), but training
    should prefer ``mlp_apply`` — its per-layer backward is cheaper."""
    from repro.kernels import dispatch as D
    return D.mlp_chain(params["layers"], x, use_fused=use_fused, mesh=mesh)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm_apply(params, x, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * params["scale"]).astype(x.dtype)


def layernorm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm_apply(params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------
def embed_init(rng, vocab: int, dim: int, dtype=jnp.float32):
    return {"table": (jax.random.normal(rng, (vocab, dim), jnp.float32) * 0.02).astype(dtype)}


def embed_apply(params, ids):
    return jnp.take(params["table"], ids, axis=0)


def embed_logits(params, x):
    """Tied-embedding output head."""
    return x @ params["table"].T


# ---------------------------------------------------------------------------
# RoPE (+ M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float = 10000.0):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., seq, heads, head_dim); positions: broadcastable (..., seq)."""
    head_dim = x.shape[-1]
    inv = rope_freqs(head_dim, theta)                     # (half,)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., seq, half)
    ang = ang[..., None, :]                                # (..., seq, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions_3d, sections: Tuple[int, int, int], theta: float = 10000.0):
    """Multimodal RoPE (Qwen2-VL): rotary dims are partitioned into
    (temporal, height, width) sections, each rotated by its own position id.

    x: (..., seq, heads, head_dim); positions_3d: (3, ..., seq).
    sections: half-dim split per modality axis, sum == head_dim // 2.
    """
    head_dim = x.shape[-1]
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    inv = rope_freqs(head_dim, theta)                     # (half,)
    # angles per modality axis, then stitch the sections together
    angs = []
    off = 0
    for axis, sec in enumerate(sections):
        p = positions_3d[axis]
        a = p[..., :, None].astype(jnp.float32) * inv[off : off + sec]
        angs.append(a)
        off += sec
    ang = jnp.concatenate(angs, axis=-1)[..., None, :]     # (..., seq, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
