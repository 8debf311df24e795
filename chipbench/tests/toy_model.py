"""A toy design model for the benchmark's own tests, named by a
configuration file's ``program_model``
(``chipbench.tests.toy_model:ToyModel``): two configuration dims, PE count
and DRAM bandwidth, over two network dims, operations and bytes.  Latency
is the slower of compute and transfer, infeasible where the PEs outrun
what the bandwidth can feed; power is static.  ``evaluate`` and
``evaluate_jax`` are one formula.  Its grids are powers of two, so the
program's float32 scan ranks candidates as the float64 reference does."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.encoding import ConfigSpace
from repro.design_models.base import DesignModel, make_dim, pow2_choices

NET_SPACE = {"OPS": pow2_choices(2**16, 2**22),
             "BYTES": pow2_choices(2**12, 2**18)}
CONFIG_SPACE = {"PEN": pow2_choices(4, 512), "BW": pow2_choices(4, 512)}
CONSTANTS = {"CLOCK_HZ": 2e8, "FEED": 4.0, "P_STATIC_W": 0.4,
             "P_PE_W": 2e-4, "P_BW_W": 1.5e-3}


def _space(grid) -> ConfigSpace:
    return ConfigSpace(dims=tuple(make_dim(n, c) for n, c in grid.items()))


def latency_power(net, cfg, xp):
    k = CONSTANTS
    ops, nbytes = net[..., 0], net[..., 1]
    pen, bw = cfg[..., 0], cfg[..., 1]
    cycles = xp.maximum(ops / pen, nbytes / bw)
    lat = xp.where(pen <= k["FEED"] * bw, cycles / k["CLOCK_HZ"], xp.inf)
    power = k["P_STATIC_W"] + k["P_PE_W"] * pen + k["P_BW_W"] * bw
    return lat, xp.where(xp.isfinite(lat), power, xp.inf)


class ToyModel(DesignModel):
    name = "toy"

    def __init__(self) -> None:
        self.space = _space(CONFIG_SPACE)
        self.net_space = _space(NET_SPACE)

    def evaluate(self, net, config):
        return latency_power(np.asarray(net, np.float64),
                             np.asarray(config, np.float64), np)

    def evaluate_jax(self, net, config):
        return latency_power(jnp.asarray(net, jnp.float32),
                             jnp.asarray(config, jnp.float32), jnp)
