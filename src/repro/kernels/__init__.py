"""Pallas TPU kernels for the framework's compute hot-spots.

- fused_mlp: dense+bias+ReLU epilogue fusion (GANDSE G/D MLP layers),
  differentiable via custom_vjp Pallas backward kernels, plus the
  whole-MLP layer-chained forward megakernel for inference paths
- flash_attention: GQA/causal/sliding-window flash attention (LM layers)

Each kernel ships with ``ref.py`` (pure-jnp oracle) and is validated in
interpret mode on CPU; ``dispatch.py`` is the single backend-aware
routing point (TPU -> Pallas, CPU/GPU -> jnp reference, and the
``force_interpret()`` hook for tests); ``ops.py`` keeps thin jit wrappers.
"""
from repro.kernels import dispatch, ops  # noqa: F401
