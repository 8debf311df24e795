"""Task-axis sharding over a device mesh — the multi-chip DSE scale-out.

Every batched DSE route (``GANDSE.explore_batch``, the fused select, the
MLP/SA/DRL device routes behind the same ``DSEMethod`` protocol) vmaps
independent task lanes, so sharding the leading task axis over the mesh's
batch axes ('pod', 'data') is pure throughput: the same jitted programs
compile to one SPMD executable over the mesh (the `jit`-with-shardings
idiom) and per-lane numerics are untouched — sharded and single-device
runs return bit-identical Selections (pinned by tests/test_shard.py).

Usage:

    from repro.core import shard
    from repro.launch.mesh import make_host_mesh

    shard.set_task_mesh(make_host_mesh())       # or the task_mesh() context
    results = engine.explore_tasks(tasks)       # now sharded over the mesh

Mechanics, shared by every route:

1. the task batch is padded to a multiple of the shard count with the
   serve batcher's repeat-last-row rule (``pad_tasks``; padded lanes are
   computed and discarded, and per-row seeds pad along so real rows keep
   their placement-independent noise streams);
2. leading-axis arrays are placed with ``put_sharded`` — a NamedSharding
   over the mesh's batch axes — so jit partitions the existing vmapped
   program across devices instead of recompiling anything new.

The fused streaming select (``core/fused_select``) keeps the same
invariant: ONLY the task axis shards.  Its candidate-tile axis is a
device-local loop dimension — every lane walks its own tiles — and the
one cross-lane value, the max(total) tile-loop bound, lowers to a
deterministic all-reduce, so sharded fused runs stay bit-identical too
(pinned by tests/test_fused_select.py::test_fused_mesh_parity).

Training rides the same mesh through ``train_gan(..., mesh=...)`` (which
defaults to the active task mesh): sharded pre-encoded batches, donated
replicated carries, gradients all-reduced over ('pod', 'data') by GSPMD.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.train.shardings import axis_size, batch_axes, norm_axes

_STATE = {"mesh": None}


def set_task_mesh(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """Install `mesh` as the process-wide task mesh (None disables
    sharding); returns the previous mesh so callers can restore it."""
    prev = _STATE["mesh"]
    _STATE["mesh"] = mesh
    return prev


def get_task_mesh() -> Optional[Mesh]:
    return _STATE["mesh"]


@contextlib.contextmanager
def task_mesh(mesh: Optional[Mesh]):
    """Scoped ``set_task_mesh`` (tests, benchmarks)."""
    prev = set_task_mesh(mesh)
    try:
        yield mesh
    finally:
        set_task_mesh(prev)


def task_axes(mesh: Optional[Mesh]) -> Optional[Tuple[str, ...]]:
    """The mesh axes the task dim shards over: ('pod', 'data') normalized
    to the axes actually present at size > 1 (None when there are none —
    e.g. a model-only or single-device mesh)."""
    if mesh is None:
        return None
    return norm_axes(batch_axes(mesh), mesh)


def n_task_shards(mesh: Optional[Mesh]) -> int:
    """How many ways the task axis splits on `mesh` (1 = unsharded)."""
    axes = task_axes(mesh)
    return axis_size(mesh, axes) if axes else 1


def active_n_shards() -> int:
    """Shard count of the active task mesh (1 when none is set) — what the
    serve micro-batcher sizes batches by."""
    return n_task_shards(get_task_mesh())


def pow2_bucket(n: int, floor: int = 2) -> int:
    """Smallest power of two >= max(n, floor): the jit-cache bucketing rule
    shared by candidate padding (``C_pad``), Algorithm 2 padding, the serve
    micro-batcher, and ``pad_tasks``, so every dynamic extent compiles at
    most log2(max) programs."""
    return 1 << (max(int(n), floor) - 1).bit_length()


def pad_rows(n: int, multiple: int) -> Optional[np.ndarray]:
    """Row gather padding `n` up to the next multiple with the batcher's
    repeat-last-row rule; None when already aligned."""
    if multiple <= 1 or n % multiple == 0:
        return None
    target = ((n + multiple - 1) // multiple) * multiple
    return np.concatenate([np.arange(n), np.full(target - n, n - 1)])


def pad_tasks(tasks, seeds: np.ndarray, mesh: Optional[Mesh] = None):
    """Pad a task batch (and its per-row seed array) to the batcher's
    bucket: ``n_shards * pow2_bucket(ceil(n / n_shards))`` (plain pow2
    when no mesh is active).  Returns ``(tasks, seeds, n_real)``.  The
    bucketing makes *direct* ``explore_batch`` calls share one jit cache
    entry across every in-bucket task count, the same contract the serve
    micro-batcher keeps for the dispatch path.  Padded rows repeat the
    last real row, seed included; their results are computed and
    discarded, and — the parity contract — they cannot perturb real rows,
    every lane being vmap-independent.
    """
    mesh = get_task_mesh() if mesh is None else mesh
    n = len(tasks)
    if n == 0:
        return tasks, seeds, 0
    shards = max(n_task_shards(mesh), 1)
    target = shards * pow2_bucket(-(-n // shards), floor=1)
    rows = pad_rows(n, target)
    if rows is None:
        return tasks, seeds, n
    return tasks.take(rows), np.asarray(seeds)[rows], n


def put_sharded(x, mesh: Optional[Mesh] = None, axis: int = 0):
    """Place `x` with its `axis` dim sharded over the mesh's task axes.

    Hands `x` back as it is (numpy stays numpy) when no mesh is active, the
    mesh has no task axes, or the dim does not divide the shard count: the
    jitted program it feeds then transfers it to the default device in its
    own C++ dispatch, with no eager transfer from Python.
    """
    mesh = get_task_mesh() if mesh is None else mesh
    axes = task_axes(mesh)
    ndim = np.ndim(x)
    if (axes is None or ndim <= axis
            or np.shape(x)[axis] % axis_size(mesh, axes) != 0):
        return x
    spec = [None] * ndim
    spec[axis] = axes
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))


def replicate(tree, mesh: Optional[Mesh] = None):
    """Replicate a pytree (params, optimizer state) across the mesh — the
    pure-DP layout whose gradients GSPMD all-reduces over the batch axes.
    No-op (identity) when no mesh is active."""
    mesh = get_task_mesh() if mesh is None else mesh
    if mesh is None:
        return tree
    return jax.device_put(tree, NamedSharding(mesh, P()))
