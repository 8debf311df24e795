"""Design Selector — Algorithm 2 (paper §6.2), exactly as published.

Scans the candidate configuration sets, keeping (L_opt, P_opt) and the
priority rules:
  scenario 1: both current objectives satisfied or both unsatisfied ->
              update only if the candidate improves BOTH;
  scenario 2: latency unsatisfied, power satisfied -> update if candidate
              improves latency while its power still satisfies PO;
  scenario 3: symmetric to 2.

The candidate metric evaluation is vectorized over the whole candidate set
(one design-model call); only the order-dependent update chain is a scan.

Models with a jnp oracle (``DesignModel.evaluate_jax``) run the whole
thing — candidate scoring AND the update chain — as one jitted
``jax.lax.scan`` on device; candidate sets are padded to the next power of
two so the jit cache stays small.  Models without a jnp port fall back to
the original host loop.  Task batches take the fused tiled route
(``core/fused_select``), whose host tail is ``selections_from_winners``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.shard import pow2_bucket
from repro.design_models.base import DesignModel


@dataclasses.dataclass
class Selection:
    cfg_idx: Optional[np.ndarray]   # (n_dims,) chosen config indices or None
    latency: float
    power: float
    satisfied: bool
    n_candidates: int

    def improvement_ratio(self, lo: float, po: float) -> Optional[float]:
        """sqrt(1/2 ((L-LO)/LO)^2 + 1/2 ((P-PO)/PO)^2) when satisfied (§7.2)."""
        if not self.satisfied:
            return None
        return float(np.sqrt(0.5 * (((self.latency - lo) / lo) ** 2
                                    + ((self.power - po) / po) ** 2)))


#: the paper allows 1% noise when judging satisfaction (§7.2); shared by
#: every DSE method (selector routes, SA, DRL) so the Table-5 comparison
#: judges all of them by the same tolerance
NOISE_TOL = 0.01


def is_satisfied(lat: float, pw: float, lo: float, po: float,
                 noise_tol: float = NOISE_TOL) -> bool:
    """§7.2 satisfaction: both metrics within (1 + noise_tol) of the
    objectives; non-finite metrics never satisfy.  The single definition
    every DSE method reports through."""
    return bool(np.isfinite(lat) and np.isfinite(pw)
                and lat <= lo * (1 + noise_tol)
                and pw <= po * (1 + noise_tol))


#: auto-route cutover: below this candidate count the host numpy loop is
#: faster than dispatching the jitted scan (see `select` docstring; a CPU
#: choice).
JAX_MIN_CANDIDATES = 512


def _algorithm2_core(model: DesignModel):
    """Traceable single-task Algorithm 2: score + update chain in one scan.

    valid marks real (non-padding) candidate rows.  Jitted for the
    per-task device route (`_algorithm2_scan`).
    """

    def run(net_idx, cand_idx, valid, lo, po):
        lat, pw = model.evaluate_jax_indices(net_idx[None, :], cand_idx)
        finite = jnp.isfinite(lat) & jnp.isfinite(pw) & valid

        def body(carry, x):
            l_opt, p_opt, chosen = carry
            lg, pg, fin, i = x
            init = (l_opt == 0.0) & (p_opt == 0.0)            # lines 7-8
            both = ((l_opt > lo) & (p_opt > po)) | ((l_opt < lo) & (p_opt < po))
            sc2 = (l_opt > lo) & (p_opt < po)                 # lines 15-18
            sc3 = (p_opt > po) & (l_opt < lo)                 # lines 20-22
            update = fin & (
                init
                | (~init & both & (lg < l_opt) & (pg < p_opt))   # lines 10-13
                | (~init & ~both & sc2 & (lg < l_opt) & (pg < po))
                | (~init & ~both & ~sc2 & sc3 & (pg < p_opt) & (lg < lo))
            )
            l_opt = jnp.where(update, lg, l_opt)              # lines 26-30
            p_opt = jnp.where(update, pg, p_opt)
            chosen = jnp.where(update, i, chosen)
            return (l_opt, p_opt, chosen), None

        n = lat.shape[0]
        carry0 = (jnp.float32(0.0), jnp.float32(0.0), jnp.int32(-1))
        xs = (lat.astype(jnp.float32), pw.astype(jnp.float32), finite,
              jnp.arange(n, dtype=jnp.int32))
        (l_opt, p_opt, chosen), _ = jax.lax.scan(body, carry0, xs)
        return l_opt, p_opt, chosen

    return run


def _algorithm2_scan(model: DesignModel):
    """Jitted per-task Algorithm 2 (cached on the model); recompiles only
    per padded candidate count."""
    return jax.jit(_algorithm2_core(model))


def _select_jax(
    model: DesignModel,
    net_idx: np.ndarray,
    cand_idx: np.ndarray,
    lat_obj: float,
    pow_obj: float,
    noise_tol: float,
) -> Selection:
    run = model.__dict__.get("_alg2_scan")
    if run is None:
        run = model.__dict__["_alg2_scan"] = _algorithm2_scan(model)
    # accept (n_net_dims,) or (1, n_net_dims) like the host route does
    net_idx = np.asarray(net_idx, np.int32).reshape(-1)
    n = cand_idx.shape[0]
    n_pad = pow2_bucket(n)                      # next pow2: bounds jit cache
    valid = np.zeros(n_pad, bool)
    valid[:n] = True
    pad = np.zeros((n_pad - n, cand_idx.shape[1]), cand_idx.dtype)
    l_opt, p_opt, chosen = run(
        jnp.asarray(net_idx),
        jnp.asarray(np.concatenate([cand_idx, pad], axis=0)),
        jnp.asarray(valid),
        jnp.float32(lat_obj), jnp.float32(pow_obj),
    )
    chosen = int(chosen)
    if chosen < 0:
        return Selection(None, np.inf, np.inf, False, n)
    # report the winner's metrics from the float64 host oracle so the
    # returned (latency, power, satisfied) are precision-consistent with
    # the host route; the scan's float32 only steered the update chain.
    lat64, pw64 = model.evaluate_indices(net_idx[None], cand_idx[chosen][None])
    l_opt, p_opt = float(lat64[0]), float(pw64[0])
    lo, po = float(lat_obj), float(pow_obj)
    satisfied = is_satisfied(l_opt, p_opt, lo, po, noise_tol)
    return Selection(cand_idx[chosen].copy(), l_opt, p_opt, satisfied, n)


def select(
    model: DesignModel,
    net_idx: np.ndarray,
    cand_idx: np.ndarray,
    lat_obj: float,
    pow_obj: float,
    noise_tol: float = NOISE_TOL,
    use_jax: Optional[bool] = None,
) -> Selection:
    """Run Algorithm 2 over the candidate set for one DSE task.

    noise_tol: the paper allows 1% noise when judging satisfaction (§7.2);
    it only affects the reported `satisfied` flag, not the selection chain.
    use_jax: None = device scan when the model has a jnp oracle AND the
    candidate set is large enough to beat a device dispatch (measured
    crossover ~512 on CPU: 3x faster at the 4096 cap, slower below ~256);
    True/False force a route.  The device route scores candidates in
    float32 (the update chain can pick a different near-tied winner than
    the float64 host loop), but the returned metrics and `satisfied` are
    always computed from the float64 host oracle on the chosen config.
    """
    if cand_idx.size == 0:
        return Selection(None, np.inf, np.inf, False, 0)
    if use_jax is None:
        use_jax = (model.has_jax_oracle
                   and cand_idx.shape[0] >= JAX_MIN_CANDIDATES)
    if use_jax:
        return _select_jax(model, net_idx, cand_idx, lat_obj, pow_obj, noise_tol)
    net = np.repeat(np.atleast_2d(net_idx), cand_idx.shape[0], axis=0)
    lat, pw = model.evaluate_indices(net, cand_idx)      # vectorized (lines 4-5)

    lo, po = float(lat_obj), float(pow_obj)
    l_opt, p_opt, chosen = 0.0, 0.0, -1
    for i in range(cand_idx.shape[0]):
        lg, pg = float(lat[i]), float(pw[i])
        if not (np.isfinite(lg) and np.isfinite(pg)):
            continue
        update = False
        if l_opt == 0.0 and p_opt == 0.0:                 # lines 7-8 (init)
            update = True
        elif (l_opt > lo and p_opt > po) or (l_opt < lo and p_opt < po):
            if lg < l_opt and pg < p_opt:                  # lines 10-13
                update = True
        elif l_opt > lo and p_opt < po:                    # lines 15-18
            if lg < l_opt and pg < po:
                update = True
        elif p_opt > po and l_opt < lo:                    # lines 20-22
            if pg < p_opt and lg < lo:
                update = True
        if update:                                         # lines 26-30
            l_opt, p_opt, chosen = lg, pg, i

    if chosen < 0:
        return Selection(None, np.inf, np.inf, False, int(cand_idx.shape[0]))
    satisfied = is_satisfied(l_opt, p_opt, lo, po, noise_tol)
    return Selection(
        cfg_idx=cand_idx[chosen].copy(),
        latency=l_opt,
        power=p_opt,
        satisfied=satisfied,
        n_candidates=int(cand_idx.shape[0]),
    )


def selections_from_winners(
    model: DesignModel,
    net_idx: np.ndarray,
    chosen,
    win_cfg,
    n_candidates,
    lat_obj,
    pow_obj,
    noise_tol: float = NOISE_TOL,
) -> List[Selection]:
    """Host tail of the fused tiled route (``core/fused_select``): given
    each task's chosen candidate rank (-1 = none feasible) and winner
    config rows, one batched float64 host-oracle call re-derives the
    reported metrics — the device float32 only steered the update chains.
    Rows with ``chosen[t] < 0`` may hold arbitrary ``win_cfg`` values;
    they are never evaluated."""
    chosen = np.asarray(chosen)
    win = np.asarray(win_cfg)
    net_idx = np.asarray(net_idx, np.int32)
    lo = np.asarray(lat_obj, np.float64).reshape(-1)
    po = np.asarray(pow_obj, np.float64).reshape(-1)
    has = chosen >= 0
    if has.any():       # one float64 host-oracle call for every winner
        lat64, pw64 = model.evaluate_indices(net_idx[has], win[has])

    out, k = [], 0
    for t in range(chosen.shape[0]):
        n = int(n_candidates[t])
        if not has[t]:
            out.append(Selection(None, np.inf, np.inf, False, n))
            continue
        l_opt, p_opt = float(lat64[k]), float(pw64[k])
        k += 1
        satisfied = is_satisfied(l_opt, p_opt, lo[t], po[t], noise_tol)
        out.append(Selection(win[t].copy(), l_opt, p_opt, satisfied, n))
    return out

