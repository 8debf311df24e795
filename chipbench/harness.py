"""What every cell shares: finding its files by name, the device check,
the compile cache, the per-layer metric readers and the result line.

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``)
and a traffic mix (``mixes/<name>.json``).  The configuration names its
design model, whose reference oracle is ``oracles/<design_model>.py``; the
mix names its driver (``drivers/<name>.py``); a per-layer metric
``<name>`` is read by ``metrics/<name>.py``.  Nothing here lists cells,
design models, mixes or metrics: adding one is adding its file.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
RESULTS_DIR = os.path.join(ROOT, "results")


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, bad file)."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str, bench: Optional[dict] = None) -> dict:
    """The workload entry, with its configuration and mix loaded."""
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    out = dict(w)
    out["cfg"] = load_json(os.path.join(ROOT, cfg_entry["file"]))
    out["mix"] = load_json(os.path.join(HERE, "mixes", w["traffic"] + ".json"))
    out["end_to_end"] = [m for m in bench["end_to_end"]
                         if name in m.get("workloads", [name])]
    out["per_layer"] = [m for m in bench["per_layer"]
                        if name in m.get("workloads", [name])]
    return out


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    return _load_module(os.path.join(HERE, "drivers", name + ".py"),
                        "chipbench_driver_" + name)


def oracle(design_model: str):
    """The design model's reference oracle module (see oracles/__init__.py),
    or BenchError naming the file to add."""
    path = os.path.join(HERE, "oracles", design_model + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no reference oracle for design model "
                         f"{design_model!r}: add {os.path.relpath(path, ROOT)}")
    return importlib.import_module("chipbench.oracles." + design_model)


def reader(metric: str) -> Callable[[dict], Optional[float]]:
    path = os.path.join(HERE, "metrics", metric + ".py")
    return _load_module(path, "chipbench_metric_" + metric.replace(".", "_")
                        ).read


def read_per_layer(c: dict, ctx: dict) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in c["per_layer"]:
        v = reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def peaks(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def use_program() -> None:
    """Put the program under test on the import path."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the program is not in this checkout ({src})")
    if src not in sys.path:
        sys.path.insert(0, src)


def compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program kept, so that only a cell's first run in a checkout compiles
    and two checkouts share nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(chips: int):
    """The accelerator the cell asks for, or BenchError."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def device_info(devs, peak_bytes: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak_bytes}


def emit(result: dict, checks: Dict[str, list]) -> None:
    """Print each compared number beside its limit as the last lines of
    stderr, then the result line (checks last in it) on stdout."""
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {k: {"value": v, "limit": l}
                        for k, (v, l) in checks.items()}
    print(json.dumps(result), flush=True)


class Clock:
    """Set-up time runs from the start of the process's main()."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def setup_s(self) -> float:
        return time.perf_counter() - self.t0


def trace_dir(workload: str, seed: int) -> str:
    """Where a traced run writes its profile (under results/, not kept)."""
    return os.path.join(RESULTS_DIR, "trace", f"{workload}-{seed}")
