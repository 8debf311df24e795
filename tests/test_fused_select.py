"""Fused streaming tiled select (core/fused_select): parity pins.

The contract under test: ``fused_select_batch`` returns Selections
bit-identical to the dense route (``enumerate_candidates_batch`` +
``select_batch``) and to the host route (``enumerate_candidates`` +
``select(use_jax=False)``), at ANY tile size — including the adversarial
cases where a streaming implementation can silently diverge:

- candidate counts at tile boundaries (tile-1, tile, tile+1) and ragged
  tails (the last tile partially padded);
- exact metric ties straddling tile boundaries (Algorithm 2 is
  first-wins: the earlier candidate must survive);
- zero-feasible tasks (all-inf oracle -> cfg_idx None) and tasks whose
  first feasible candidate sits mid-tile;
- ragged per-task counts inside one batch.

``MixModel`` keeps every metric an exact small integer in float32, so
the float32 device chains and the float64 host loop make identical
accept decisions — the comparisons below are exact equality, never
almost-equal.  Small moduli force many exact ties.

Each case runs through both tile decodes: the compare-select chain
(``"chain"``: every group at most ``SELECT_CHAIN_MAX`` wide) and the
gather fallback (``"gather"``: the same space plus one group past the
cut-off, which the probabilities pin to a single random choice, so the
candidate sets, tile boundaries and ties are the chain case's).

The mesh test (4 fake devices, shard4 CI job) pins sharded == unsharded
bit-identically: the task axis shards, the tile axis never does.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal CI image — seeded-random fallback
    from _mini_hypothesis import given, settings, strategies as st

from repro.core import shard
from repro.core.encoding import ConfigDim, ConfigSpace
from repro.core.explorer import (_enum_core, enumerate_candidates,
                                 enumerate_candidates_batch)
from repro.core.fused_select import (SELECT_CHAIN_MAX, _fused_batch,
                                     fused_select_batch)
from repro.core.selector import select, select_batch
from repro.design_models.base import DesignModel
from repro.design_models.dnnweaver import DnnWeaverModel
from repro.design_models.im2col import Im2colModel
from repro.launch.mesh import make_host_mesh

from _hlo import loop_ops

N_DEV = 4
multidevice = pytest.mark.skipif(
    len(jax.devices()) < N_DEV,
    reason=f"needs >= {N_DEV} devices; run with "
           f"XLA_FLAGS=--xla_force_host_platform_device_count={N_DEV}")


#: a group one past the chain cut-off sends a space down the gather decode
WIDE = SELECT_CHAIN_MAX + 1


def _space(sizes):
    return ConfigSpace(dims=tuple(
        ConfigDim(f"d{k}", tuple(float(v) for v in range(n)))
        for k, n in enumerate(sizes)))


class MixModel(DesignModel):
    """Deterministic synthetic model over an arbitrary space: metrics are
    small-integer hashes of the config values — exact in float32, so
    device (f32) and host (f64) chains agree bit-for-bit.  Small moduli
    force exact metric ties; ``inf_mod`` marks every config whose mix is
    divisible by it infeasible (inf_mod=1 -> nothing feasible).  ``wide``
    appends a group of ``WIDE`` choices (the gather decode)."""

    name = "mix"

    def __init__(self, sizes, lat_mod=61.0, pw_mod=53.0, inf_mod=0.0,
                 wide=False):
        sizes = tuple(sizes) + ((WIDE,) if wide else ())
        self.space = _space(sizes)
        self.net_space = ConfigSpace(dims=(ConfigDim("n", (0.0, 1.0)),))
        self._w = np.arange(1, len(sizes) + 1, dtype=np.float64) * 3.0 + 2.0
        self.lat_mod, self.pw_mod, self.inf_mod = lat_mod, pw_mod, inf_mod

    def _mix(self, xp, config):
        s = (config * xp.asarray(self._w, config.dtype)).sum(axis=-1)
        lat = xp.mod(s * 7.0 + 3.0, self.lat_mod) + 1.0
        pw = xp.mod(s * 5.0 + 11.0, self.pw_mod) + 1.0
        if self.inf_mod:
            bad = xp.mod(s, self.inf_mod) == 0
            lat = xp.where(bad, xp.inf, lat)
            pw = xp.where(bad, xp.inf, pw)
        return lat, pw

    def evaluate(self, net, config):
        return self._mix(np, np.asarray(config, np.float64))

    def evaluate_jax(self, net, config):
        return self._mix(jnp, config)


def _probs(model, n_tasks, seed, peak=0.9):
    """Random per-group dirichlet probs (T, onehot_width), scaled so the
    per-group max is `peak` — thresholds then slice ragged employed sets."""
    rng = np.random.default_rng(seed)
    cols = []
    for dim in model.space.dims:
        if dim.n == WIDE:
            break
        p = rng.dirichlet(np.ones(len(dim.choices)), size=n_tasks)
        cols.append(p / p.max(axis=1, keepdims=True) * peak)
    return _widen(model, np.concatenate(cols, axis=1), rng, peak)


def _widen(model, probs, rng, peak=0.9):
    """Append a wide model's last group: `peak` on one random choice a
    task, 0 elsewhere, so it employs exactly that choice."""
    if model.space.dims[-1].n == WIDE:
        pin = np.eye(WIDE)[rng.integers(0, WIDE, probs.shape[0])] * peak
        probs = np.concatenate([probs, pin], axis=1)
    return probs.astype(np.float32)


def _routes(model, probs, thresh, cap, lo, po, tile):
    """(fused, dense, host) Selections for the same inputs."""
    t = probs.shape[0]
    net = np.zeros((t, 1), np.int32)
    fused = fused_select_batch(model, net, probs, thresh, cap, lo, po,
                               tile=tile)
    cand, valid, counts = enumerate_candidates_batch(
        model.space, probs, thresh, cap)
    dense = select_batch(model, net, cand, valid, counts, lo, po)
    host = []
    for i in range(t):
        c = enumerate_candidates(model.space, probs[i], thresh, cap)
        host.append(select(model, net[i], c, float(lo[i]), float(po[i]),
                           use_jax=False))
    return fused, dense, host


def _assert_same(a, b):
    assert a.n_candidates == b.n_candidates
    assert a.satisfied == b.satisfied
    if a.cfg_idx is None:
        assert b.cfg_idx is None
        return
    np.testing.assert_array_equal(a.cfg_idx, b.cfg_idx)
    assert a.latency == b.latency and a.power == b.power   # exact, not close


# three fixed models per decode so jit caches are reused across examples
MODELS = {decode: {
    "plain": MixModel((5, 4, 3, 4), wide=wide),
    "ties": MixModel((6, 5, 4), lat_mod=7.0, pw_mod=5.0, wide=wide),
    "holes": MixModel((4, 4, 4, 3), inf_mod=7.0, wide=wide),
} for decode, wide in (("chain", False), ("gather", True))}
DECODES = pytest.mark.parametrize("decode", sorted(MODELS))


# ---------------------------------------------------------------------------
# tiled enumeration == host itertools.product at tile boundaries
# ---------------------------------------------------------------------------
def _tiled_enum(space, probs, thresh, cap, tile):
    """Materialize candidates window-by-window with the exact per-tile
    arithmetic of the fused program's tile_step (same ``_enum_core``)."""
    masks_core, radix_core = _enum_core(space)
    keep, counts, total = jax.jit(masks_core)(
        jnp.asarray(probs), jnp.float32(thresh), jnp.int32(cap))
    table, stride = jax.jit(radix_core)(keep, counts)
    total = np.asarray(total)
    out = []
    for t in range(probs.shape[0]):
        rows = []
        for j0 in range(0, int(total[t]), tile):
            j = jnp.arange(j0, j0 + tile, dtype=jnp.int32)
            digit = (j[:, None] // stride[t][None, :]) % counts[t][None, :]
            cand = jnp.take_along_axis(table[t], digit.T, axis=-1).T
            rows.append(np.asarray(cand, np.int32))
        cat = (np.concatenate(rows)[: int(total[t])] if rows
               else np.zeros((0, space.n_dims), np.int32))
        out.append(cat)
    return out


@pytest.mark.parametrize("sizes,thresh", [
    ((7,), 0.0),          # total = tile - 1
    ((8,), 0.0),          # total = tile
    ((3, 3), 0.0),        # total = tile + 1
    ((2, 4), 0.0),        # total = tile, multi-group
    ((5, 4, 3), 0.0),     # 60 = 7 full tiles + ragged 4-row tail
    ((5, 4, 3), 0.35),    # ragged employed sets (threshold slices groups)
])
def test_tiled_enumeration_matches_itertools_product(sizes, thresh):
    space = _space(sizes)
    rng = np.random.default_rng(sum(sizes))
    probs = np.concatenate(
        [rng.uniform(0.4, 1.0, (2, n)).astype(np.float32) for n in sizes],
        axis=1)
    tiled = _tiled_enum(space, probs, thresh, 1 << 12, tile=8)
    for t in range(probs.shape[0]):
        host = enumerate_candidates(space, probs[t], thresh, 1 << 12)
        np.testing.assert_array_equal(tiled[t], host)
        # cross-check the host route really is itertools.product order
        if thresh == 0.0:
            full = np.array(list(itertools.product(
                *[range(n) for n in sizes])), np.int32)
            np.testing.assert_array_equal(host, full)


@given(st.lists(st.integers(1, 6), min_size=1, max_size=4),
       st.integers(0, 2 ** 31 - 1), st.integers(4, 11))
@settings(max_examples=25, deadline=None)
def test_tiled_enumeration_property(sizes, seed, tile):
    """Random spaces, random ragged employed sets, tiles straddling the
    counts every which way — window arithmetic == itertools.product."""
    space = _space(tuple(sizes))
    rng = np.random.default_rng(seed)
    probs = np.concatenate(
        [rng.uniform(0.0, 1.0, (1, n)).astype(np.float32) for n in sizes],
        axis=1)
    (tiled,) = _tiled_enum(space, probs, 0.5, 1 << 12, tile=tile)
    host = enumerate_candidates(space, probs[0], 0.5, 1 << 12)
    np.testing.assert_array_equal(tiled, host)


# ---------------------------------------------------------------------------
# Selection parity: fused == dense == host
# ---------------------------------------------------------------------------
@DECODES
@given(st.sampled_from(sorted(MODELS["chain"])), st.integers(0, 2 ** 31 - 1),
       st.sampled_from([0.02, 0.2, 0.5]), st.sampled_from([4, 8, 16]),
       st.floats(2.0, 50.0), st.floats(2.0, 40.0))
@settings(max_examples=20, deadline=None)
def test_fused_dense_host_parity(decode, name, seed, thresh, tile, lo0, po0):
    model = MODELS[decode][name]
    probs = _probs(model, 4, seed)
    rng = np.random.default_rng(seed + 1)
    lo = np.float64(lo0) + rng.integers(0, 8, 4)    # integer-valued: exact
    po = np.float64(po0) + rng.integers(0, 8, 4)    # in f32 like the metrics
    fused, dense, host = _routes(model, probs, thresh, 256, lo, po, tile)
    counts = {s.n_candidates for s in fused}
    for f, d, h in zip(fused, dense, host):
        _assert_same(f, d)
        _assert_same(f, h)
    assert len(counts) >= 1   # ragged batches occur across examples


@DECODES
def test_all_ties_first_candidate_wins_across_tiles(decode):
    """Every candidate identical -> Algorithm 2 accepts only the first
    finite row; a tile reduction that re-orders within a tile (or lets a
    later tile overwrite an equal carry) breaks this."""
    model = MixModel((4, 4, 4), lat_mod=1.0, pw_mod=1.0,   # all (1+s%1)=1.0
                     wide=decode == "gather")
    probs = _widen(model, np.full((2, 12), 0.9), np.random.default_rng(1))
    lo = np.array([10.0, 0.5])      # satisfied and unsatisfied regimes
    po = np.array([10.0, 0.5])
    for tile in (3, 4, 64):
        fused, dense, host = _routes(model, probs, 0.1, 256, lo, po, tile)
        for f, d, h in zip(fused, dense, host):
            _assert_same(f, d)
            _assert_same(f, h)
            np.testing.assert_array_equal(f.cfg_idx[:3], [0, 0, 0])


@DECODES
def test_first_feasible_mid_tile_and_zero_feasible(decode):
    """Leading-infeasible runs (winner sits mid-tile / in a later tile)
    and fully infeasible tasks (cfg_idx None, counts still reported)."""
    wide = decode == "gather"
    holes = MixModel((4, 4, 4), inf_mod=2.0, wide=wide)   # ~half infeasible
    dead = MixModel((4, 4, 4), inf_mod=1.0, wide=wide)    # nothing feasible
    probs = _probs(holes, 3, seed=5)
    lo = np.array([20.0, 3.0, 40.0])
    po = np.array([20.0, 3.0, 40.0])
    for tile in (4, 8, 128):
        fused, dense, host = _routes(holes, probs, 0.05, 256, lo, po, tile)
        for f, d, h in zip(fused, dense, host):
            _assert_same(f, d)
            _assert_same(f, h)
    fused, dense, host = _routes(dead, probs, 0.05, 256, lo, po, 8)
    for f, d, h in zip(fused, dense, host):
        assert f.cfg_idx is None and f.n_candidates == h.n_candidates
        _assert_same(f, d)
        _assert_same(f, h)


@DECODES
def test_caps_beyond_dense_limit_accepted(decode):
    """The fused route takes caps past the dense materialization bound
    (2**20); the dense route still refuses them."""
    model = MODELS[decode]["plain"]
    probs = _probs(model, 2, seed=9)
    lo = po = np.array([20.0, 20.0])
    net = np.zeros((2, 1), np.int32)
    sels = fused_select_batch(model, net, probs, 0.02, 1 << 26, lo, po,
                              tile=64)
    assert all(s.cfg_idx is not None for s in sels)
    with pytest.raises(AssertionError):
        enumerate_candidates_batch(model.space, probs, 0.02, 1 << 26)


def _accepting_tiles(model, probs, thresh, cap, lo, po, tile):
    """Host recount: the tiles in which the sequential Algorithm 2 chain
    accepts a row, for one task (float64 on MixModel's exact metrics)."""
    cand = enumerate_candidates(model.space, probs, thresh, cap)
    lat, pw = model.evaluate_indices(np.zeros((len(cand), 1), np.int32), cand)
    l_opt = p_opt = 0.0
    tiles = set()
    for i, (lg, pg) in enumerate(zip(lat, pw)):
        if not (np.isfinite(lg) and np.isfinite(pg)):
            continue
        init = l_opt == 0.0 and p_opt == 0.0
        both = (l_opt > lo and p_opt > po) or (l_opt < lo and p_opt < po)
        sc2 = l_opt > lo and p_opt < po
        sc3 = p_opt > po and l_opt < lo
        if (init or (both and lg < l_opt and pg < p_opt)
                or (not both and sc2 and lg < l_opt and pg < po)
                or (not both and not sc2 and sc3 and pg < p_opt
                    and lg < lo)):
            l_opt, p_opt = lg, pg
            tiles.add(i // tile)
    return tiles


@DECODES
@pytest.mark.parametrize("name,tile", [("plain", 4), ("plain", 16),
                                       ("ties", 8), ("holes", 4)])
def test_tile_and_replay_counters(decode, name, tile):
    """The program counts ceil(max(total) / tile) tile steps per call, as
    replay steps exactly those in which some task's chain accepts a row
    (one task's accepting tiles, the union of them over a batch), and as
    gather-free steps all of them on the chain decode, none on the
    gather decode.  Selections do not change with the counters on."""
    model = MODELS[decode][name]
    probs = _probs(model, 4, seed=21)
    rng = np.random.default_rng(22)
    lo = np.float64(10.0) + rng.integers(0, 20, 4)
    po = np.float64(10.0) + rng.integers(0, 20, 4)
    net = np.zeros((4, 1), np.int32)
    for rows in ([0], [1], [0, 1, 2, 3]):
        stats = {"select_tiles": 0, "select_replay_tiles": 0,
                 "select_gather_free_tiles": 0}
        sels = fused_select_batch(model, net[rows], probs[rows], 0.05, 256,
                                  lo[rows], po[rows], tile=tile, stats=stats)
        plain = fused_select_batch(model, net[rows], probs[rows], 0.05, 256,
                                   lo[rows], po[rows], tile=tile)
        for a, b in zip(sels, plain):
            _assert_same(a, b)
        total = max(s.n_candidates for s in sels)
        assert stats["select_tiles"] == -(-total // tile)
        assert 0 <= stats["select_replay_tiles"] <= stats["select_tiles"]
        want = set().union(*(_accepting_tiles(
            model, probs[t], 0.05, 256, lo[t], po[t], tile) for t in rows))
        assert stats["select_replay_tiles"] == len(want)
        assert stats["select_gather_free_tiles"] == (
            stats["select_tiles"] if decode == "chain" else 0)
    # the counts add up over calls
    fused_select_batch(model, net, probs, 0.05, 256, lo, po, tile=tile,
                       stats=stats)
    assert stats["select_tiles"] == 2 * -(-total // tile)
    assert stats["select_gather_free_tiles"] == (
        stats["select_tiles"] if decode == "chain" else 0)


@pytest.mark.parametrize("model_cls", [Im2colModel, DnnWeaverModel])
def test_shipped_models_decode_gather_free(model_cls):
    """Both benchmarked design spaces take the chain decode: every tile
    step counts as gather-free."""
    model = model_cls()
    probs = _probs(model, 3, seed=31)
    net = np.zeros((3, model.net_space.n_dims), np.int32)
    stats = {"select_tiles": 0, "select_replay_tiles": 0,
             "select_gather_free_tiles": 0}
    fused_select_batch(model, net, probs, 0.05, 512, np.full(3, 1e-3),
                       np.full(3, 5.0), tile=64, stats=stats)
    assert stats["select_tiles"] >= 2
    assert stats["select_gather_free_tiles"] == stats["select_tiles"]


@pytest.mark.parametrize("model,gather_free", [
    (Im2colModel(), True), (DnnWeaverModel(), True),
    (MixModel((5, 4, 3), wide=True), False)],
    ids=["im2col", "dnnweaver", "wide"])
def test_tile_loop_holds_no_candidate_gather(model, gather_free):
    """The compiled tile loop (its body and the replay branch) gathers
    nothing candidate-sized at T = 64, tile = 1024: no (T, tile, n_dims)
    index lookup, no (T, tile) value lookup.  Only the once-per-call
    tables gather.  A space past the chain cut-off keeps its gathers,
    which shows the check sees them."""
    t, tile = 64, 1024
    f32, i32 = jnp.float32, jnp.int32
    hlo = _fused_batch(model, model.space, tile).lower(
        jax.ShapeDtypeStruct((t, model.space.onehot_width), f32),
        jax.ShapeDtypeStruct((), f32), jax.ShapeDtypeStruct((), i32),
        jax.ShapeDtypeStruct((t, model.net_space.n_dims), i32),
        jax.ShapeDtypeStruct((t,), f32), jax.ShapeDtypeStruct((t,), f32),
    ).compile().as_text()
    big = [g for g in loop_ops(hlo, "gather") if g[1] >= t * tile]
    assert (big == []) == gather_free, big


@multidevice
@DECODES
def test_fused_mesh_parity(decode):
    """Task-sharded fused run == single-device fused run, bit-identical
    (the tile axis is never sharded; max(total) becomes an all-reduce)."""
    model = MixModel((6, 5, 4, 3), wide=decode == "gather")
    probs = _probs(model, 8, seed=13)
    rng = np.random.default_rng(14)
    lo = np.float64(10.0) + rng.integers(0, 20, 8)
    po = np.float64(10.0) + rng.integers(0, 20, 8)
    net = np.zeros((8, 1), np.int32)
    base = fused_select_batch(model, net, probs, 0.05, 512, lo, po, tile=16)
    with shard.task_mesh(make_host_mesh()):
        sharded = fused_select_batch(model, net, probs, 0.05, 512, lo, po,
                                     tile=16)
    for a, b in zip(base, sharded):
        _assert_same(a, b)
