"""THE backend-aware dispatch point for the fused-MLP fast path.

Every consumer of the fused kernels (nn/layers, core/gan, core/explorer,
baselines/mlp, serve) routes through this module, so the decision "Pallas
or jnp reference?" lives in exactly one place:

- TPU backend       -> Pallas kernels (compiled);
- CPU / GPU         -> pure-jnp reference (identical semantics);
- ``use_fused``     -> overrides the backend default: ``False`` forces the
  jnp route even on TPU, ``True`` requests fusion (still a no-op off-TPU,
  where the compiled Pallas path does not exist); ``None`` = backend auto;
- the ``force_interpret()`` test hook -> the Pallas kernel bodies run in
  interpret mode on any backend, so CPU tests check what the kernels
  compute.  It is the only way onto interpret mode: no consumer takes an
  ``interpret`` argument.  Whether the chip's compiler accepts the kernels
  is checked separately, by compiling for a described TPU
  (tests/test_tpu_compile.py);
- ``mesh`` -> XLA cannot partition a Mosaic kernel, so inside a program
  that spans several devices every kernel call runs under ``shard_map``:
  rows split over the mesh's task axes ('pod', 'data') when they divide
  the shard count, replicated otherwise, weights replicated (shard_map's
  transpose sums their gradients over the shards).  Callers pass the
  mesh their program runs on (the train step's data-parallel mesh, the
  explorer's task mesh); None = one device.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import fused_mlp as _fm
from repro.kernels import ref as _ref
from repro.train.shardings import axis_size, batch_axes, norm_axes

#: test hook: when True, every dispatch runs the Pallas kernels in
#: interpret mode (flip via force_interpret(); traces must happen inside
#: the context — already-jitted closures keep the route they traced with)
_FORCE_INTERPRET = False


@contextlib.contextmanager
def force_interpret(enable: bool = True):
    """Route every dispatch through the Pallas kernels in interpret mode —
    the CPU test hook that drives the *kernel* code through jitted
    consumers (train step, explorer forward) without a TPU."""
    global _FORCE_INTERPRET
    old, _FORCE_INTERPRET = _FORCE_INTERPRET, enable
    try:
        yield
    finally:
        _FORCE_INTERPRET = old


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_enabled(use_fused: Optional[bool]) -> bool:
    """The dispatch rule: explicit flag wins, None means backend auto."""
    return on_tpu() if use_fused is None else bool(use_fused)


def _route(use_fused: Optional[bool]):
    """-> (use_pallas, interpret) after applying the rule above.

    An explicit ``use_fused=False`` beats the ``force_interpret()`` hook: a
    consumer pinned to the jnp reference stays there — the documented
    "False forces jnp" contract, which keeps hook-driven parity tests
    honest."""
    if use_fused is False:
        return False, False
    if _FORCE_INTERPRET:
        return True, True
    return fused_enabled(use_fused) and on_tpu(), False


def kernel_route_active(use_fused: Optional[bool] = None) -> bool:
    """True when ``dense``/``mlp_chain`` with these args would run the
    Pallas kernels (compiled or interpret) rather than the jnp reference —
    the one predicate callers gate on, so it can never drift from the
    route the dispatchers actually take."""
    return _route(use_fused)[0]


def _per_shard(kernel: Callable, mesh: Optional[Mesh], x, *params):
    """``kernel(x, *params)`` for a row-independent kernel, run on every
    device of ``mesh`` under shard_map (plain call for one device)."""
    if mesh is None or mesh.size == 1:
        return kernel(x, *params)
    axes = norm_axes(batch_axes(mesh), mesh)
    rows = P(axes) if axes and x.shape[0] % axis_size(mesh, axes) == 0 \
        else P()
    # check_vma=False: pallas_call outputs carry no varying-axes type; the
    # transpose then psums the replicated params' cotangents itself
    return jax.shard_map(kernel, mesh=mesh, in_specs=(rows,) + (P(),) *
                         len(params), out_specs=rows,
                         check_vma=False)(x, *params)


def dense(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
          relu: bool = True, use_fused: Optional[bool] = None,
          mesh: Optional[Mesh] = None) -> jnp.ndarray:
    """[relu](x @ w + b); x may carry leading batch dims (flattened to M).
    Differentiable on both routes (the Pallas route via its custom_vjp)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    pallas, interp = _route(use_fused)
    if pallas:
        kernel = functools.partial(_fm.fused_dense, relu=relu,
                                   interpret=interp)
        y = _per_shard(kernel, mesh, x2, w, b)
    elif relu:
        y = _ref.fused_dense_relu(x2, w, b)
    else:
        y = _ref.fused_dense(x2, w, b)
    return y.reshape(*lead, w.shape[-1])


def mlp_chain(layers: List[dict], x: jnp.ndarray, *,
              use_fused: Optional[bool] = None,
              mesh: Optional[Mesh] = None) -> jnp.ndarray:
    """Whole-MLP forward (hidden ReLU, linear head) from a
    ``mlp_init``-style layer list.  The fused route is the layer-chained
    megakernel (activations never leave VMEM between layers) — the
    inference fast path; the reference route is the plain jnp loop."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    pallas, interp = _route(use_fused)
    if pallas:
        ws = tuple(p["w"] for p in layers)
        bs = tuple(p["b"] for p in layers)
        kernel = functools.partial(_fm.fused_mlp, interpret=interp)
        y = _per_shard(kernel, mesh, x2, ws, bs)
    else:
        y = x2
        for p in layers[:-1]:
            y = jax.nn.relu(y @ p["w"] + p["b"])
        y = y @ layers[-1]["w"] + layers[-1]["b"]
    return y.reshape(*lead, layers[-1]["w"].shape[-1])
