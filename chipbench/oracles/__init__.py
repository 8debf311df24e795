"""Each design model's plain reference oracle, one file a design model:
``oracles/<design_model>.py``, found by the configuration file's
``design_model`` (``harness.oracle``).  Adding a design model to the
benchmark is adding its file.

A module exposes

    evaluate(k: dict, net: np.ndarray, cfg: np.ndarray) -> (latency, power)

- ``k`` is the configuration file's ``oracle_constants``;
- ``net`` and ``cfg`` are float64 values (not choice indices), network
  parameters and configuration in the order of the file's ``net_space``
  and ``config_space``, whose leading dims broadcast;
- latency (s) and power (W) are float64 in the broadcast shape, ``+inf``
  where a configuration is infeasible.

Rules:

- an oracle imports nothing from ``repro``, the program under test;
- it is written from the published description of the design model;
- it takes its constants from the configuration file only.
"""
