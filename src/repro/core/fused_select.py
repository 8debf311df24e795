"""Streaming tiled select: enumerate -> score -> select as ONE program.

The batched Algorithm 2 route.  Materializing every candidate as a
(T, C_pad, n_dims) tensor and walking it with a sequential length-C_pad
scan would scale memory and latency linearly with the candidate cap and
need a mid-dispatch host sync to pick C_pad.  This module instead fuses
the three stages into one jitted program that loops over fixed-size
candidate *tiles*:

- each tile step decodes its tile-sized index window by *incremental*
  mixed-radix arithmetic: the in-tile offset digits are divmod-decoded
  once per call (via the ``explorer._enum_core`` radices) and every tile
  adds them to the running tile-base digits with a carry-propagating
  compare/subtract —
  zero integer divisions inside the loop (runtime-divisor divmod over
  (T, tile, n_dims) was ~half the route's wall time) and the full
  tensor is never materialized, so peak candidate memory is
  O(T * tile * n_dims) at ANY cap;
- the digits become configuration values.  Where every choice group has
  at most ``SELECT_CHAIN_MAX`` choices (every shipped design space), no
  gather runs inside the loop: once per call each task's kept choices
  become a (T, max_group, n_dims) float32 value table, and each dim's
  (T, tile) digit plane picks its value by an unrolled compare-select
  chain over that dim's slots.  Wider groups take the value through the
  per-task slot table and the constant choice table, two gathers a
  tile.  Both give the very float32 values ``values_from_indices_jax``
  gives, so the oracle sees identical inputs;
- the model's float32 ``evaluate_jax`` scores the tile, with the
  network's values converted once per call;
- an exact fast-forward of the Algorithm-2 update chain folds the tile
  into the running per-task winner.

Exactness.  Algorithm 2's update chain is path-dependent — whether a row
is accepted depends on the (L_opt, P_opt) carry it meets, so no
carry-independent per-tile argmin/total-order reduction can match the
sequential chain.  Instead the *accept test itself* is vectorized: under
a fixed carry, the chain's next accepted row is simply the first row
whose update predicate holds, so a while-loop of [mask -> jump to first
set bit -> reload carry] replays the sequential chain bit-exactly —
including first-wins tie order — in O(accepted rows) vectorized passes
instead of O(tile) scalar steps.  Accepted rows are rare (each must
improve on the last; measured 1-3 per task over ~900 tiles at cap
2**20), and the accept mask under a fixed carry is cheap to build
row-vectorized — the chain's case split (init/both/sc2/sc3) depends
only on per-task scalars, so the mask is a handful of broadcast
compares that XLA fuses straight into the oracle chain.  The tile step
therefore computes that exact mask once and a ``lax.cond`` runs the
replay loop ONLY on tile steps where some task of the batch provably
accepts a row: the common-step cost is one fused mask reduction, no loop
machinery.  The cond is batch-wide (``jnp.any`` over every task), so one
task's accept replays the step for all of them: 64-task im2col batches
at cap 2**16 replay 57% of their tile steps (TPU v5e).  The program
counts the steps that replay (``stats["select_replay_tiles"]``).

The tile-loop trip count is ceil(max(total) / tile) computed ON DEVICE —
no ``np.asarray`` mid-dispatch (the GL112 bug class), no recompile (the
program is static in everything but the task-bucket shape), and no
wasted tiles when candidate sets are far below the cap.  Warm serve
dispatch is one uninterrupted device program.

Selections are bit-identical to the host route (pinned by
``tests/test_fused_select.py``), and winner metrics are re-derived from
the float64 host oracle through ``selections_from_winners``.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import shard
from repro.core.encoding import ConfigSpace
from repro.core.explorer import _PROD_LIM, _enum_core
from repro.core.selector import NOISE_TOL, Selection, selections_from_winners
from repro.design_models.base import DesignModel
from repro.utils import trace

#: default tile width — peak candidate memory is O(T * tile * n_dims)
#: regardless of max_candidates, which is how caps up to _PROD_LIM = 2**26
#: fit
FUSED_TILE = 1024

#: widest choice group decoded by a compare-select chain: the chain costs
#: (n - 1) selects a dim per candidate, unrolled into both the tile loop
#: and its replay branch, so it bounds the traced program (and its compile
#: time) at 31 selects a dim; every shipped space stays far below (im2col
#: 7, DNNWeaver 8, tpu_mesh 7: its DP and EP groups).  Wider groups, up to
#: the route's 1024, keep the table gather.  A bound on program size, not a
#: tuned speed.
SELECT_CHAIN_MAX = 32


def _gather_free(space: ConfigSpace) -> bool:
    """Whether the program decodes tiles by compare-select chains (no
    gather in the tile loop) rather than by table gathers."""
    return space.max_group_size <= SELECT_CHAIN_MAX


def _fused_batch(model: DesignModel, space: ConfigSpace, tile: int):
    """Build the jitted enumerate->score->select program for one
    (model, tile); cached on the model by `fused_select_batch`."""
    masks_core, radix_core = _enum_core(space)
    rows = jnp.arange(tile, dtype=jnp.int32)
    n_dims = space.n_dims
    gather_free = _gather_free(space)

    def radix_add(base, add, counts):
        # mixed-radix add of per-dim digit planes with the last dim least
        # significant (itertools.product order, the `radix_core` strides);
        # both addends are digit-wise < counts so the ripple carry is at
        # most 1, and the dropped carry-out wraps mod prod(counts) exactly
        # like the divmod form does for indices past the raw product
        digits = [None] * n_dims
        carry = jnp.int32(0)
        for d in range(n_dims - 1, -1, -1):
            s = base[d] + add[d] + carry
            carry = (s >= counts[d]).astype(jnp.int32)
            digits[d] = s - carry * counts[d]
        return digits

    def fold_tile(lo, po, lat, pw, valid, j0, l_opt, p_opt, chosen):
        # exact Algorithm-2 fold of one task's tile (see module docstring):
        # under a fixed carry the accept mask is the update predicate of
        # selector._algorithm2_core, row-vectorized; the first set bit at
        # or after `pos` is the next row the sequential chain accepts.
        fin = jnp.isfinite(lat) & jnp.isfinite(pw) & valid

        def accept(l_opt, p_opt, pos):
            init = (l_opt == 0.0) & (p_opt == 0.0)
            both = ((l_opt > lo) & (p_opt > po)) | ((l_opt < lo) & (p_opt < po))
            sc2 = (l_opt > lo) & (p_opt < po)
            sc3 = (p_opt > po) & (l_opt < lo)
            upd = fin & (
                init
                | (~init & both & (lat < l_opt) & (pw < p_opt))
                | (~init & ~both & sc2 & (lat < l_opt) & (pw < po))
                | (~init & ~both & ~sc2 & sc3 & (pw < p_opt) & (lat < lo))
            )
            return upd & (rows >= pos)

        def cond(state):
            l_opt, p_opt, _chosen, pos = state
            return jnp.any(accept(l_opt, p_opt, pos))

        def body(state):
            l_opt, p_opt, chosen, pos = state
            i = jnp.argmax(accept(l_opt, p_opt, pos)).astype(jnp.int32)
            return lat[i], pw[i], j0 + i, i + jnp.int32(1)

        l_opt, p_opt, chosen, _ = jax.lax.while_loop(
            cond, body, (l_opt, p_opt, chosen, jnp.int32(0)))
        return l_opt, p_opt, chosen

    def run(probs, thresh, cap, net_idx, lo, po):
        t = probs.shape[0]
        keep, counts, total = masks_core(probs, thresh, cap)
        table, stride = radix_core(keep, counts)
        n_tiles = (jnp.max(total) + (tile - 1)) // tile   # device: no sync
        cnt = [counts[:, d] for d in range(n_dims)]
        cnt_rows = [c[:, None] for c in cnt]
        # the ONLY divmod decodes, once per call: the in-tile offset digits
        # (n_dims planes of (T, tile), lane-dense) and the per-tile-step
        # digit increment (T,) per dim.  The barrier keeps XLA from sinking
        # the cheap-looking iota divmod back into the loop body (the TPU
        # compiler does, once no gather consumes it): integer division in
        # every tile step, and a program ten times slower to compile
        off_dig = jax.lax.optimization_barrier(
            [(rows[None, :] // stride[:, d, None]) % cnt_rows[d]
             for d in range(n_dims)])
        step_dig = [(jnp.int32(tile) // stride[:, d]) % cnt[d]
                    for d in range(n_dims)]
        # loop-invariant values, once per call: the network's and, on the
        # gather-free side, each task's kept choices as float32 values
        # vals[t, k, d] = choices_d[table[t, d, k]] (one small gather)
        net_vals = model.net_space.values_from_indices_jax(net_idx)[:, None, :]
        if gather_free:
            vals = space.values_from_indices_jax(table.transpose(0, 2, 1))

        def decode(digits):
            # a tile's configuration values (T, tile, n_dims) from its
            # per-dim digit planes.  Gather-free: an unrolled compare-select
            # chain over the dim's slots picks vals[t, digit, d] — the very
            # float32 that the table gather then the choice gather return.
            if gather_free:
                cols = []
                for d, dim in enumerate(space.dims):
                    v = jnp.broadcast_to(vals[:, 0, d, None], (t, tile))
                    for k in range(1, dim.n):
                        v = jnp.where(digits[d] == k, vals[:, k, d, None], v)
                    cols.append(v)
                return jnp.stack(cols, axis=-1)
            cand = jnp.take_along_axis(table, jnp.stack(digits, axis=1),
                                       axis=-1).transpose(0, 2, 1)
            return space.values_from_indices_jax(cand.astype(jnp.int32))

        def decode_and_score(base_dig):
            # the mixed-radix digits of a tile-sized index window, via
            # divmod-free incremental add of the tile-base digits
            digits = radix_add([b[:, None] for b in base_dig], off_dig,
                               cnt_rows)
            lat, pw = model.evaluate_jax(net_vals, decode(digits))
            return lat.astype(jnp.float32), pw.astype(jnp.float32)

        def tile_step(k, carry):
            l_opt, p_opt, chosen, base_dig, replays, feasible = carry
            j0 = (k * tile).astype(jnp.int32)
            valid = (j0 + rows)[None, :] < total[:, None]
            latf, pwf = decode_and_score(base_dig)
            # the EXACT accept mask of the update chain under the incoming
            # carry (== fold_tile's first while cond): the case split is
            # per-task scalars, only the metric compares are per-row, so
            # this fuses into one decode->oracle->mask reduction — the
            # replay runs only on tile steps where some task provably
            # accepts a row; the cond is batch-wide, so one task's accept
            # replays the whole batch (`replays` counts those steps)
            fin = jnp.isfinite(latf) & jnp.isfinite(pwf) & valid
            init = (l_opt == 0.0) & (p_opt == 0.0)
            both = ((l_opt > lo) & (p_opt > po)) | ((l_opt < lo) & (p_opt < po))
            sc2 = (l_opt > lo) & (p_opt < po)
            sc3 = (p_opt > po) & (l_opt < lo)
            lt_l = latf < l_opt[:, None]
            lt_p = pwf < p_opt[:, None]
            upd = fin & (
                init[:, None]
                | ((~init & both)[:, None] & lt_l & lt_p)
                | ((~init & ~both & sc2)[:, None] & lt_l
                   & (pwf < po[:, None]))
                | ((~init & ~both & ~sc2 & sc3)[:, None] & lt_p
                   & (latf < lo[:, None])))

            def replay(c):
                # recompute the tile INSIDE the rare branch: handing latf/
                # pwf to lax.cond as operands would force them (and the
                # whole float32 decode and oracle chain) to materialize
                # every tile, breaking the common path's single fusion —
                # recomputing from the (T,) carry digit planes keeps the
                # cond's operands tiny and costs one extra decode and
                # oracle pass on each accepting tile step
                lat2, pw2 = decode_and_score(base_dig)
                return jax.vmap(
                    fold_tile, in_axes=(0, 0, 0, 0, 0, None, 0, 0, 0))(
                    lo, po, lat2, pw2, valid, j0, *c)

            hit = jnp.any(upd)
            l_opt, p_opt, chosen = jax.lax.cond(
                hit, replay, lambda c: c, (l_opt, p_opt, chosen))
            # each task's feasible candidates, counted once a tile step
            # from the common path's mask (a replay recounts nothing);
            # per task, so the count stays exact int32 at any cap
            return (l_opt, p_opt, chosen,
                    radix_add(base_dig, step_dig, cnt),
                    replays + hit.astype(jnp.int32),
                    feasible + jnp.sum(fin, axis=1, dtype=jnp.int32))

        carry0 = (jnp.zeros(t, jnp.float32), jnp.zeros(t, jnp.float32),
                  jnp.full((t,), -1, jnp.int32),
                  [jnp.zeros(t, jnp.int32)] * n_dims, jnp.int32(0),
                  jnp.zeros(t, jnp.int32))
        _, _, chosen, _, replays, feasible = jax.lax.fori_loop(
            0, n_tiles, tile_step, carry0)
        # winner configs from the same mixed radix; rows with chosen < 0
        # yield arbitrary values here and are masked by the host tail
        jw = jnp.maximum(chosen, 0)[:, None]
        digit_w = (jw // stride) % counts
        win = jnp.take_along_axis(table, digit_w[:, :, None], axis=-1)[..., 0]
        return (chosen, win.astype(jnp.int32), total, n_tiles, replays,
                feasible)

    return jax.jit(run)


def fused_select_batch(
    model: DesignModel,
    net_idx: np.ndarray,
    probs,
    thresh: float,
    max_candidates: int,
    lat_obj,
    pow_obj,
    noise_tol: float = NOISE_TOL,
    tile: int = FUSED_TILE,
    stats: Optional[Dict[str, int]] = None,
) -> List[Selection]:
    """Batched Algorithm 2 straight from generator probs, streaming tiles.

    net_idx (T, n_net_dims), probs (T, onehot_width) (host or device, as
    produced by ``Explorer.generator_probs_device``), objectives (T,).
    Requires a jnp oracle (``model.has_jax_oracle``).  Task t's Selection
    is bit-identical to the host route's (``enumerate_candidates`` +
    ``select``), at any tile size.

    Under an active task mesh (``shard.set_task_mesh``) with T a multiple
    of the shard count, the inputs land task-sharded and the one fused
    program partitions across devices; the tile axis is never sharded, so
    lane numerics — and winners — are unchanged (the max(total) tile
    bound becomes a deterministic all-reduce).

    ``stats``, when given, gains the call's tile steps (``select_tiles``,
    ceil(max(total) / tile)), those that took the replay branch
    (``select_replay_tiles``), fetched with the winners in one transfer,
    and those decoded without a gather (``select_gather_free_tiles``: all
    of them at max group size <= ``SELECT_CHAIN_MAX``, else none); and
    the candidates the tasks scanned (``select_scanned``, padding rows
    included) and those the oracle found feasible (``select_feasible``,
    finite latency and power), from the same transfer.  The batch's
    ``dse.host_tail`` span carries the call's two candidate counts as
    metadata.
    """
    assert model.has_jax_oracle, "fused route needs a jnp oracle"
    assert model.space.max_group_size <= 1024 and \
        1 <= max_candidates <= _PROD_LIM, \
        "fused route needs max group size <= 1024 and cap <= 2**26"
    assert tile >= 1
    with trace.span("dse.select"):
        cache = model.__dict__.setdefault("_fused_select", {})
        run = cache.get(tile)
        if run is None:
            run = cache[tile] = _fused_batch(model, model.space, tile)
        net_idx = np.asarray(net_idx, np.int32)
        lo = np.asarray(lat_obj, np.float64).reshape(-1)
        po = np.asarray(pow_obj, np.float64).reshape(-1)
        out = run(
            shard.put_sharded(probs), np.float32(thresh),
            np.int32(max_candidates), shard.put_sharded(net_idx),
            shard.put_sharded(lo.astype(np.float32)),
            shard.put_sharded(po.astype(np.float32)),
        )
    with trace.span("dse.sync"):
        chosen, win_cfg, total, n_tiles, replays, feasible = \
            jax.device_get(out)
    counts = {"select_tiles": int(n_tiles),
              "select_replay_tiles": int(replays),
              "select_gather_free_tiles":
                  int(n_tiles) if _gather_free(model.space) else 0,
              "select_scanned": int(np.sum(total, dtype=np.int64)),
              "select_feasible": int(np.sum(feasible, dtype=np.int64))}
    if stats is not None:
        for k, v in counts.items():
            stats[k] = stats.get(k, 0) + v
    with trace.span("dse.host_tail", select_scanned=counts["select_scanned"],
                    select_feasible=counts["select_feasible"]):
        return selections_from_winners(model, net_idx, chosen, win_cfg,
                                       total, lo, po, noise_tol)
