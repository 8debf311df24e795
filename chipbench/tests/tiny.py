"""A cell cut to a size the CPU runs in seconds, for the benchmark's own
tests: 2 x 64 G and D, small pools and batches, the device check steered
to the CPU.  The harness's code paths are the chip run's."""
from __future__ import annotations

import os
import types

import jax

from chipbench import harness
from chipbench.compiles import CompileCounter

_COUNTER = []


def counter() -> CompileCounter:
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


# The training cell is staged, not measured: its files are kept, its entry
# is not in BENCHMARK.json (PERF.md, Open questions), so the tests add it.
STAGED = {"im2col-train": (
    {"name": "im2col-train", "config": "gandse-im2col",
     "traffic": "train-2epoch-64k", "chips": 1, "why": "staged"},
    {"name": "train_step_ms", "unit": "ms", "better": "lower",
     "source": "host_clock", "workloads": ["im2col-train"]})}


def cell(name: str) -> dict:
    bench = harness.benchmark()
    if name in STAGED:
        workload, metric = STAGED[name]
        bench["workloads"].append(workload)
        bench["end_to_end"].append(metric)
    c = harness.cell(name, bench)
    c["cfg"].update(g_hidden_layers=2, g_neurons=64, d_hidden_layers=2,
                    d_neurons=64)
    if c["mix"]["driver"] == "train":
        c["cfg"]["batch_size"] = 128
        c["mix"].update(rows=1024)
    else:
        c["mix"].update(dataset_rows=512, task_pool=512, check_sample=4,
                        clients=8, max_batch=8, rate_rps=20,
                        max_candidates=min(c["mix"]["max_candidates"], 4096))
    return c


def on_cpu(monkeypatch) -> None:
    """Let the harness run on the CPU with the v5e's peaks."""
    table = harness.load_json(os.path.join(harness.HERE, "peaks.json"))
    monkeypatch.setattr(harness, "devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "peaks", lambda kind: table["TPU v5 lite"])


def run(c: dict, seed: int = 2**31 + 7, seconds: float = 1.0, trace=0):
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    return harness.driver(c["mix"]["driver"]).run(c, args, harness.Clock(),
                                                  counter())
