"""Micro-batch coalescing: many independent in-flight requests -> few
pow2-bucketed `DSETask` dispatches.

The batched exploration path (`GANDSE.explore_batch` and the baselines'
device routes) compiles one program per (batch size, C_pad) pair, so the
batcher reuses the `C_pad` bucketing idea on the batch axis: a micro-batch
of m requests is padded to the next power of two by repeating its last row
(padding rows are computed and discarded — every task lane is vmapped
-independent, so they cannot perturb real rows), keeping the jit cache at
<= log2(max_batch) batch-size entries no matter how ragged the arrival
pattern is.

Per-request seeds ride along as a (T,) array (`row_seeds` array form), so
a request's Selection never depends on which micro-batch it landed in or
at which position.

Under an active task mesh the padded size is additionally a multiple of
the shard count — ``n_shards * pow2_bucket(ceil(m / n_shards))`` — so one
sharded dispatch serves the whole micro-batch with every device lane full
(``n_shards=None`` reads ``shard.active_n_shards()`` at formation time;
1 shard reproduces the old sizing exactly).

Thread safety: every queue operation holds one internal lock, so the
concurrent front end can admit from submitter threads while the former
thread pops micro-batches — admit/next_batch/requeue/shed interleave
atomically and no request is ever lost or double-popped (pinned by
tests/test_serve_concurrency.py).
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict, deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.core import shard
from repro.dataset.generator import DSETask
from repro.serve.request import DSERequest


@dataclasses.dataclass
class MicroBatch:
    """One dispatchable unit: the real requests plus the padded task batch.

    ``tasks``/``seeds`` carry ``padded_size`` rows; only the first
    ``len(requests)`` are real, the rest repeat the last real row and are
    dropped after dispatch.
    """

    model_name: str
    requests: List[DSERequest]
    tasks: DSETask
    seeds: np.ndarray            # (padded_size,) int64 per-row noise seeds
    #: per-model params generation the batch was formed under (stamped by
    #: `DSEServer._pop_ready`).  `publish_batch` compares it against the
    #: live counter: a swap landing between the lock-free execute and the
    #: publish invalidated the model's cache entries, so a mismatched
    #: batch still responds but must not re-cache its (old-params) results.
    params_gen: int = 0
    #: server-wide sequence number (stamped with ``params_gen``): the
    #: ``batch`` id on the batch's trace spans (`repro.utils.trace`)
    seq: int = -1

    @property
    def n_real(self) -> int:
        return len(self.requests)

    @property
    def padded_size(self) -> int:
        return len(self.tasks)


class MicroBatcher:
    """Per-model FIFO admission queues + micro-batch formation."""

    def __init__(self, max_batch: int = 64, pad_pow2: bool = True,
                 n_shards: Optional[int] = None):
        assert max_batch >= 1
        self.max_batch = int(max_batch)
        self.pad_pow2 = bool(pad_pow2)
        #: None = follow the active task mesh (read per batch formation, so
        #: installing a mesh mid-serve takes effect on the next dispatch)
        self.n_shards = n_shards if n_shards is None else int(n_shards)
        self._queues: "OrderedDict[str, Deque[DSERequest]]" = OrderedDict()
        self._lock = threading.RLock()

    def _shards(self) -> int:
        k = self.n_shards if self.n_shards is not None \
            else shard.active_n_shards()
        return max(1, int(k))

    def admit(self, req: DSERequest) -> None:
        with self._lock:
            self._queues.setdefault(req.model_name, deque()).append(req)

    def requeue_front(self, reqs: List[DSERequest]) -> None:
        """Push popped requests back to the head of their queue in their
        original order (dispatch-failure recovery: nothing is lost, the
        next step retries them)."""
        with self._lock:
            for req in reversed(reqs):
                self._queues.setdefault(req.model_name,
                                        deque()).appendleft(req)

    def pending(self, model_name: Optional[str] = None) -> int:
        with self._lock:
            if model_name is not None:
                return len(self._queues.get(model_name, ()))
            return sum(len(q) for q in self._queues.values())

    def models_with_work(self) -> List[str]:
        with self._lock:
            return [m for m, q in self._queues.items() if q]

    def shed(self, predicate: Callable[[DSERequest], bool]
             ) -> List[DSERequest]:
        """Remove (and return) every queued request matching ``predicate``,
        preserving FIFO order among survivors and pruning drained queues.
        The admission-control hook: the server sheds expired-deadline
        requests here, *before* they can occupy a dispatch slot."""
        with self._lock:
            out: List[DSERequest] = []
            for name in list(self._queues):
                q = self._queues[name]
                kept = deque()
                for req in q:
                    (out if predicate(req) else kept).append(req)
                if kept:
                    self._queues[name] = kept
                else:
                    del self._queues[name]
            return out

    def next_batch(self, model_name: Optional[str] = None,
                   rotate: Optional[bool] = None) -> Optional[MicroBatch]:
        """Pop up to ``max_batch`` queued requests (FIFO; round-robin over
        models when ``model_name`` is None) and coalesce them into one
        padded micro-batch.  Returns None when nothing is queued.

        A queue drained by the pop is pruned from the table (the dict used
        to grow one dead entry per retired model under model churn), and
        the round-robin order rotates only on round-robin pops (``rotate``
        defaults to exactly that) — a targeted ``next_batch(model_name=…)``
        does not steal the models behind the target their turn.  The
        server's backoff-aware formation passes an explicit model *and*
        ``rotate=True``: it pre-selects the round-robin head itself (to
        skip models in a retry-backoff window) and the rotation must still
        happen.
        """
        with self._lock:
            round_robin = model_name is None
            if round_robin:
                work = self.models_with_work()
                if not work:
                    return None
                model_name = work[0]
            if rotate is None:
                rotate = round_robin
            q = self._queues.get(model_name)
            if not q:
                return None
            reqs = [q.popleft() for _ in range(min(self.max_batch, len(q)))]
            if not q:
                del self._queues[model_name]
            elif rotate:
                # rotate to the back so multi-model queues share dispatches
                self._queues.move_to_end(model_name)

        m = len(reqs)
        k = self._shards()
        per_shard = -(-m // k)       # ceil(m / k)
        if self.pad_pow2:
            per_shard = shard.pow2_bucket(per_shard, floor=1)
        # padding repeats the last real row; the batch is built from the
        # requests' admission-time rows with one stack and three arrays
        rows = reqs + [reqs[-1]] * (per_shard * k - m)
        tasks = DSETask(
            net_idx=np.stack([r.net_idx for r in rows]),
            lat_obj=np.array([r.lat_obj for r in rows], np.float64),
            pow_obj=np.array([r.pow_obj for r in rows], np.float64))
        seeds = np.array([r.seed for r in rows], np.int64)
        return MicroBatch(model_name=model_name, requests=reqs,
                          tasks=tasks, seeds=seeds)
