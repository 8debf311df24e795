"""The serving request path at batch cost: a request's cache key and task
row are built once at admission, formation stacks them, and publication
reuses them.

- `DSERequest.key` equals (and hashes like) `cache_key` of the same
  fields, for any index dtype, and a caller mutating its array after
  ``submit`` changes neither the key nor the formed row;
- `MicroBatcher.next_batch` gives the tasks, seeds and dtypes of the
  per-request construction it replaced (1-row `DSETask`s concatenated,
  then the last row repeated to the pow2 bucket times the shard count);
- the response map and the undrained outbox stay at
  ``response_retention``, and ``drain`` keeps production order past it;
- malformed submissions raise the documented messages.

A stub engine answers every batch, so no device program runs here.
"""
import numpy as np
import pytest

from repro.core import shard
from repro.core.dse_api import DSEResult, cache_key
from repro.core.selector import Selection
from repro.dataset.generator import DSETask
from repro.design_models.dnnweaver import DnnWeaverModel
from repro.serve import DSERequest, DSEServer, MicroBatcher, ServeConfig


class _StubEngine:
    """Answers row t of every batch with a result naming its seed."""

    def __init__(self, model):
        self.model = model

    def explore_tasks(self, tasks, seed=0):
        return [DSEResult(Selection(None, float(s), 0.0, False, 0),
                          float(lo), float(po), 0.0)
                for s, lo, po in zip(np.broadcast_to(seed, len(tasks)),
                                     tasks.lat_obj, tasks.pow_obj)]


@pytest.fixture(scope="module")
def model():
    return DnnWeaverModel()


def _server(model, **kw):
    srv = DSEServer(ServeConfig(**kw))
    srv.register(_StubEngine(model))
    return srv


def _old_key(model_name, net_idx, lat_obj, pow_obj, seed):
    """The key's construction before it was built from one tolist()."""
    return (str(model_name),
            tuple(int(v) for v in np.asarray(net_idx).reshape(-1)),
            float(lat_obj), float(pow_obj), int(seed))


@pytest.mark.parametrize("dtype", [np.int64, np.int32, np.int16, np.uint8,
                                   np.float64])
def test_request_key_equals_cache_key(dtype):
    net = np.array([4, 0, 3, 2, 1, 2]).astype(dtype)
    req = DSERequest(rid=0, model_name="m", net_idx=net, lat_obj=1e-3,
                     pow_obj=2.5, seed=2**40 + 3)
    want = cache_key("m", net, 1e-3, 2.5, 2**40 + 3)
    assert req.key == want and hash(req.key) == hash(want)
    assert req.key == _old_key("m", net, 1e-3, 2.5, 2**40 + 3)
    assert [type(v) for v in req.key[1]] == [int] * net.size
    assert [type(v) for v in req.key[2:]] == [float, float, int]


@pytest.mark.parametrize("dtype", [np.int64, np.int32])
def test_key_and_row_survive_caller_mutation(model, dtype):
    srv = _server(model)
    buf = np.array([4, 0, 3, 2, 1, 2], dtype)
    srv.submit(model.name, buf, 1e-3, 2.5, seed=7)
    want = cache_key(model.name, buf.copy(), 1e-3, 2.5, 7)
    buf[:] = 0                                   # caller reuses the buffer
    batch = srv.form_batch()
    (req,) = batch.requests
    assert req.key == want and hash(req.key) == hash(want)
    np.testing.assert_array_equal(batch.tasks.net_idx[0],
                                  [4, 0, 3, 2, 1, 2])


def _reference_batch(reqs, pad_pow2, n_shards):
    """The per-request formation `next_batch` used to do."""
    m = len(reqs)
    rows = [r.as_task() for r in reqs]
    tasks = DSETask(net_idx=np.concatenate([t.net_idx for t in rows]),
                    lat_obj=np.concatenate([t.lat_obj for t in rows]),
                    pow_obj=np.concatenate([t.pow_obj for t in rows]))
    seeds = np.array([r.seed for r in reqs], np.int64)
    per_shard = -(-m // n_shards)
    if pad_pow2:
        per_shard = shard.pow2_bucket(per_shard, floor=1)
    target = per_shard * n_shards
    if target > m:
        rows = np.concatenate([np.arange(m), np.full(target - m, m - 1)])
        tasks, seeds = tasks.take(rows), seeds[rows]
    return tasks, seeds


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("pad_pow2", [True, False])
@pytest.mark.parametrize("m", [1, 3, 33, 64])
def test_next_batch_matches_per_request_construction(model, m, pad_pow2,
                                                     n_shards):
    rng = np.random.default_rng(m)
    sizes = np.asarray(model.net_space.group_sizes)
    reqs = [DSERequest(rid=i, model_name=model.name,
                       net_idx=rng.integers(0, sizes, dtype=np.int64),
                       lat_obj=float(rng.uniform(1e-4, 1e-2)),
                       pow_obj=float(rng.uniform(0.5, 5.0)),
                       seed=int(rng.integers(0, 2**62)))
            for i in range(m)]
    mb = MicroBatcher(max_batch=64, pad_pow2=pad_pow2, n_shards=n_shards)
    for r in reqs:
        mb.admit(r)
    batch = mb.next_batch()
    tasks, seeds = _reference_batch(reqs, pad_pow2, n_shards)
    assert [r.rid for r in batch.requests] == list(range(m))
    assert batch.n_real == m
    for got, want in ((batch.tasks.net_idx, tasks.net_idx),
                      (batch.tasks.lat_obj, tasks.lat_obj),
                      (batch.tasks.pow_obj, tasks.pow_obj),
                      (batch.seeds, seeds)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
    assert batch.tasks.net_idx.dtype == np.int64
    assert batch.tasks.lat_obj.dtype == batch.tasks.pow_obj.dtype \
        == np.float64


@pytest.mark.parametrize("retention", [1, 5, 64])
def test_retention_bounds_responses_and_outbox(model, retention):
    srv = _server(model, max_batch=4, cache_capacity=0,
                  response_retention=retention)
    n = 3 * retention + 7
    rids = [srv.submit(model.name, np.zeros(6, np.int64), 1e-3, 2.5,
                       seed=s) for s in range(n)]
    while srv.step():
        assert len(srv._responses) <= retention
        assert len(srv._outbox) <= retention
    out = srv.drain()
    # production order (FIFO batches), the newest `retention` of them
    assert [r.rid for r in out] == rids[-retention:]
    assert [r.seed for r in out] == list(range(n))[-retention:]
    assert [r.result.selection.latency for r in out] == \
        [float(s) for s in range(n)][-retention:]
    assert len(srv._responses) == retention and not srv._outbox
    assert srv.drain() == []
    more = [srv.submit(model.name, np.zeros(6, np.int64), 1e-3, 2.5,
                       seed=n + s) for s in range(2)]
    assert [r.rid for r in srv.drain()] == more[-retention:]


@pytest.mark.parametrize("net, message", [
    (np.zeros(5, np.int64), "net_idx has 5 dims, 'dnnweaver' expects 6"),
    (np.zeros(99, np.int32), "net_idx has 99 dims, 'dnnweaver' expects 6"),
    ([0, 0, 0, 0, 0, 3], "net_idx [0, 0, 0, 0, 0, 3] out of range for "
                         "'dnnweaver' (sizes [5, 5, 4, 4, 3, 3])"),
    ([0, -1, 0, 0, 0, 0], "net_idx [0, -1, 0, 0, 0, 0] out of range for "
                          "'dnnweaver' (sizes [5, 5, 4, 4, 3, 3])"),
], ids=["few-dims", "many-dims", "too-large", "negative"])
def test_malformed_submit_messages(model, net, message):
    srv = _server(model)
    with pytest.raises(ValueError) as e:
        srv.submit(model.name, net, 1e-3, 2.5)
    assert str(e.value) == message
    assert srv.batcher.pending() == 0 and srv.stats["submitted"] == 0
