"""Public jit'd entry points for the Pallas kernels.

On TPU the Pallas path is used; elsewhere the pure-XLA fallback keeps
semantics identical.  ``flash_attention(interpret=True)`` forces its Pallas
kernel body to execute in Python for validation; the dense aliases follow
``dispatch`` (interpret mode only under its ``force_interpret()`` hook).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import dispatch as _dispatch
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return _dispatch.on_tpu()


def fused_dense_relu(x: jnp.ndarray, w: jnp.ndarray,
                     b: jnp.ndarray) -> jnp.ndarray:
    """relu(x @ w + b); x may have leading batch dims (flattened to M).
    Thin alias over ``dispatch.dense`` (the single dispatch point)."""
    return _dispatch.dense(x, w, b, relu=True)


def fused_dense(x: jnp.ndarray, w: jnp.ndarray,
                b: jnp.ndarray) -> jnp.ndarray:
    return _dispatch.dense(x, w, b, relu=False)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, q_offset: int = 0,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """(B, H, S, D) x (B, Hkv, S, D)^2 -> (B, H, S, D)."""
    if interpret or (interpret is None and _on_tpu()):
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, interpret=bool(interpret))
    return _ref.flash_attention(q, k, v, causal=causal, window=window,
                                q_offset=q_offset)
