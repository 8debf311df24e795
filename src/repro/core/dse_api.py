"""High-level GANDSE API: the four phases of Fig. 4.

- Training phase: ``GANDSE.train`` (once per design template / design model)
- Parsing phase:  ``parse_network`` (abstract layer description -> net params)
- Exploration:    ``GANDSE.explore`` (G inference -> candidates -> Algorithm 2)
  and its batched device-resident twin ``GANDSE.explore_batch`` (one
  dispatch chain for a whole task batch; what ``explore_tasks`` routes to)
- Implementation: ``GANDSE.emit_config`` (structured artifact; stands in for
  the paper's RTL generator, see DESIGN.md §2)
"""
from __future__ import annotations

import dataclasses
import time
from typing import (Dict, List, Optional, Protocol, Sequence, Union,
                    runtime_checkable)

import numpy as np

from repro.core import gan as G
from repro.core import shard
from repro.core.explorer import Explorer, ExplorerConfig, row_seeds  # noqa: F401
# (row_seeds re-exported: the per-row seed convention lives next to
# task_seeds so the device and host routes cannot drift apart)
from repro.core.fused_select import fused_select_batch
from repro.core.selector import Selection, select
from repro.core.train import TrainState, train_gan
from repro.dataset.generator import Dataset, DSETask, generate_dataset
from repro.design_models.base import DesignModel
from repro.utils import trace


def parse_network(desc: Dict[str, float], model: DesignModel) -> np.ndarray:
    """Parsing phase: {'IC':64, 'OC':32, ...} -> net-space indices.

    Values are snapped to the nearest legal sampled value (the dataset
    generator covers the space evenly, §7.1.2), so a second parse of the
    snapped values is a fixed point.
    """
    names = [d.name for d in model.net_space.dims]
    vals = np.array([[float(desc[n]) for n in names]])
    return model.net_space.indices_from_values(vals)[0]


#: scalar-or-per-row-array seed accepted by every batch entry point
SeedLike = Union[int, np.ndarray]


def cache_key(model_name: str, net_idx: np.ndarray, lat_obj: float,
              pow_obj: float, seed: int) -> tuple:
    """Hashable identity of one DSE task row: what the serving result cache
    keys on.  Two submissions with equal keys are guaranteed the same
    Selection by the batched-vs-sequential parity contract (the per-task
    noise key is PRNGKey(seed), independent of batch placement), so a
    cached result is indistinguishable from a recompute — until the
    engine's params change (`DSEServer.swap` invalidates the model's
    entries).  The net part is one ``tolist()`` of the index array:
    Python ints, equal and hash-equal whatever its integer dtype (any
    other dtype goes through ``int``, as it always did).
    """
    flat = np.asarray(net_idx).reshape(-1)
    net = flat.tolist()
    if flat.dtype.kind not in "iu":
        net = map(int, net)
    return (str(model_name), tuple(net), float(lat_obj), float(pow_obj),
            int(seed))


@dataclasses.dataclass
class DSEResult:
    selection: Selection
    lat_obj: float
    pow_obj: float
    dse_seconds: float

    @property
    def satisfied(self) -> bool:
        return self.selection.satisfied

    @property
    def improvement_ratio(self) -> Optional[float]:
        return self.selection.improvement_ratio(self.lat_obj, self.pow_obj)


@runtime_checkable
class DSEMethod(Protocol):
    """What every DSE engine speaks — GANDSE and all baselines.

    The comparison harness (experiments/run_comparison.py) and Table-5
    benchmarks treat methods uniformly through this protocol:

    - ``train(n_data, iters, seed=, ds=, log_every=)``: fit on a (shared)
      dataset; model-free methods (SA, random search) accept the call as a
      no-op so one loop drives every method.
    - ``explore(net_idx, lat_obj, pow_obj, seed=)``: one DSE task ->
      ``DSEResult``.
    - ``explore_tasks(tasks, seed=)``: a task batch -> ``List[DSEResult]``.
      Methods with a device route serve the batch in one dispatch chain and
      fall back to the sequential host loop for models without a jnp oracle
      (the ``use_jax_oracle`` rule).  ``seed`` is a scalar (row t explores
      with seed + t) or a (T,) per-row seed array — the array form is how
      the serving layer keeps coalesced requests' results independent of
      micro-batch placement.
    """

    model: DesignModel
    method_name: str

    def train(self, n_data: int, iters: int, seed: int = 0,
              ds: Optional[Dataset] = None, log_every: int = 0) -> object: ...

    def explore(self, net_idx: np.ndarray, lat_obj: float, pow_obj: float,
                seed: int = 0) -> "DSEResult": ...

    def explore_tasks(self, tasks: DSETask, seed: SeedLike = 0
                      ) -> List["DSEResult"]: ...


class GANDSE:
    """End-to-end framework object for one design template (design model)."""

    method_name = "GANDSE"

    def __init__(self, model: DesignModel, gan_cfg: Optional[G.GANConfig] = None,
                 explorer_cfg: Optional[ExplorerConfig] = None):
        self.model = model
        n_net = model.net_space.n_dims
        self.gan_cfg = gan_cfg or G.GANConfig(n_net=n_net)
        assert self.gan_cfg.n_net == n_net
        self.explorer_cfg = explorer_cfg or ExplorerConfig()
        self.ds: Optional[Dataset] = None
        self.state: Optional[TrainState] = None
        self._explorer: Optional[Explorer] = None
        #: the fused select's tile steps, those that took the replay
        #: branch and those decoded without a gather, and the candidates
        #: scanned and found feasible, summed over every batch this
        #: engine explored
        self.stats: Dict[str, int] = {"select_tiles": 0,
                                      "select_replay_tiles": 0,
                                      "select_gather_free_tiles": 0,
                                      "select_scanned": 0,
                                      "select_feasible": 0}

    # ---- training phase ----------------------------------------------------
    def train(self, n_data: int, iters: int, seed: int = 0, log_every: int = 0,
              ds: Optional[Dataset] = None) -> TrainState:
        self.ds = ds if ds is not None else generate_dataset(self.model, n_data, seed=seed)
        self.state = train_gan(self.model, self.ds, self.gan_cfg, iters=iters,
                               seed=seed, log_every=log_every)
        self.attach(self.ds, self.state.g_params)
        return self.state

    @property
    def g_params(self) -> Optional[Dict]:
        """Currently attached generator params (None before
        ``train()``/``attach()``) — what a checkpoint of the serving state
        should save (the online loop's generation-0 checkpoint)."""
        return None if self._explorer is None else self._explorer.g_params

    def attach(self, ds: Dataset, g_params: Dict) -> Explorer:
        """Serving entry: wire a dataset (for its normalizers) and trained
        generator params into the explorer without retraining — e.g. params
        restored from a checkpoint, or a hot-swap after an out-of-band
        retrain.  The compiled G inference is shared across Explorer
        instances (cached on (space, gan_cfg)), so a swap never recompiles.
        """
        self.ds = ds
        self._explorer = Explorer(self.model, ds, g_params, self.gan_cfg,
                                  self.explorer_cfg)
        return self._explorer

    # ---- exploration phase ---------------------------------------------------
    def explore(self, net_idx: np.ndarray, lat_obj: float, pow_obj: float,
                seed: int = 0) -> DSEResult:
        assert self._explorer is not None, "call train() or attach() first"
        t0 = time.time()
        cands = self._explorer.candidates(net_idx, lat_obj, pow_obj, seed=seed)
        sel = select(self.model, net_idx, cands, lat_obj, pow_obj)
        return DSEResult(sel, float(lat_obj), float(pow_obj), time.time() - t0)

    def explore_batch(self, tasks: DSETask,
                      seed: SeedLike = 0) -> List[DSEResult]:
        """Batched device-resident exploration: vmapped G inference ->
        fused streaming enumerate/score/select (``core/fused_select``) —
        one uninterrupted device program for the whole task batch, with
        zero mid-dispatch host syncs and candidate caps up to 2**26.
        Task i returns the same Selection as
        ``explore(tasks.net_idx[i], ..., seed=seed + i)`` — or
        ``seed=seed[i]`` when ``seed`` is a (T,) per-task array — identical
        candidate sets always; the winner too, except when `explore` routes
        a small candidate set through the float64 host loop and two
        near-tied candidates differ by less than float32 resolution (the
        same caveat as `select`'s device route).  dse_seconds is the
        amortized per-task wall-clock (total / n_tasks).  Models without a
        jnp oracle fall back to the sequential host route.

        The task batch is padded to its pow2 bucket (``shard.pad_tasks``,
        repeat-last-row, results discarded), so every in-bucket task count
        reuses one compiled program — the same jit-cache contract the
        serve micro-batcher keeps.  Under an active task mesh
        (``shard.set_task_mesh``) the padded size is additionally a
        multiple of the shard count and the whole chain — G inference,
        candidate enumeration, Algorithm 2 — runs task-sharded across the
        mesh.  Selections are bit-identical to the single-device run.
        """
        assert self._explorer is not None, "call train() or attach() first"
        n_tasks = int(tasks.net_idx.shape[0])
        if n_tasks == 0:
            return []
        if not self.model.has_jax_oracle:
            return self._explore_seq(tasks, seed)
        t0 = time.time()
        seeds = row_seeds(seed, n_tasks)
        tasks_p, seeds, n_real = shard.pad_tasks(tasks, seeds)
        with trace.span("dse.gfwd"):
            probs = self._explorer.generator_probs_device(
                tasks_p.net_idx, tasks_p.lat_obj, tasks_p.pow_obj,
                seed=seeds)
        sels = fused_select_batch(
            self.model, tasks_p.net_idx, probs,
            self.explorer_cfg.prob_threshold,
            self.explorer_cfg.max_candidates,
            tasks_p.lat_obj, tasks_p.pow_obj,
            tile=self.explorer_cfg.select_tile, stats=self.stats)
        per_task = (time.time() - t0) / n_real
        return [
            DSEResult(sel, float(tasks.lat_obj[i]), float(tasks.pow_obj[i]),
                      per_task)
            for i, sel in enumerate(sels[:n_real])
        ]

    def explore_tasks(self, tasks: DSETask, seed: SeedLike = 0,
                      batched: Optional[bool] = None) -> List[DSEResult]:
        """Explore a task batch.  batched=None (default) routes through
        `explore_batch` whenever the model has a jnp oracle; False forces
        the sequential per-task loop (same results, one dispatch chain per
        task).  seed: scalar or (T,) per-task array (see `explore_batch`)."""
        if batched is None:
            batched = self.model.has_jax_oracle
        if batched:
            return self.explore_batch(tasks, seed=seed)
        return self._explore_seq(tasks, seed)

    def _explore_seq(self, tasks: DSETask, seed: SeedLike) -> List[DSEResult]:
        seeds = row_seeds(seed, tasks.net_idx.shape[0])
        return [
            self.explore(tasks.net_idx[i], tasks.lat_obj[i], tasks.pow_obj[i],
                         seed=seeds[i])
            for i in range(tasks.net_idx.shape[0])
        ]

    # ---- implementation phase ------------------------------------------------
    def emit_config(self, result: DSEResult) -> Dict:
        """Structured design artifact (stands in for RTL emission)."""
        sel = result.selection
        assert sel.cfg_idx is not None
        vals = self.model.space.values_from_indices(sel.cfg_idx[None])[0]
        return {
            "design_model": self.model.name,
            "config": {d.name: v for d, v in zip(self.model.space.dims, vals.tolist())},
            "predicted": {"latency_s": sel.latency, "power_w": sel.power},
            "objectives": {"latency_s": result.lat_obj, "power_w": result.pow_obj},
            "satisfied": sel.satisfied,
        }


def summarize(results: Sequence[DSEResult]) -> Dict[str, float]:
    """Table-5-style metrics: satisfied count, improvement ratio, DSE time,
    candidate count, error stds (Fig. 5).

    Defined (and silent — no numpy RuntimeWarning) for every input: an
    empty result list reports zero counts/times, and metrics that average
    over an empty subset (improvement ratio with nothing satisfied, error
    stds with nothing feasible) report NaN.
    """
    n = len(results)
    sat = [r for r in results if r.satisfied]
    irs = [r.improvement_ratio for r in sat if r.improvement_ratio is not None]
    lerr = [ (r.selection.latency - r.lat_obj) / r.lat_obj
             for r in results if np.isfinite(r.selection.latency) ]
    perr = [ (r.selection.power - r.pow_obj) / r.pow_obj
             for r in results if np.isfinite(r.selection.power) ]
    return {
        "n_tasks": n,
        "n_satisfied": len(sat),
        "improvement_ratio": float(np.mean(irs)) if irs else float("nan"),
        "dse_time_s": float(np.mean([r.dse_seconds for r in results])) if n else 0.0,
        "n_candidates": float(np.mean([r.selection.n_candidates
                                       for r in results])) if n else 0.0,
        "lat_err_std": float(np.std(lerr)) if lerr else float("nan"),
        "pow_err_std": float(np.std(perr)) if perr else float("nan"),
    }
