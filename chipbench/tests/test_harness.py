"""The harness finds cells, mixes and metrics by name from files alone,
and refuses to report without the accelerator a cell asks for."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = harness.ROOT


def _copy(tmp_path):
    dst = tmp_path / "repo"
    shutil.copytree(harness.HERE, dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    return dst


def test_new_mix_and_metric_by_files_alone(tmp_path):
    dst = _copy(tmp_path)
    (dst / "chipbench" / "mixes" / "dummy-mix.json").write_text(json.dumps(
        {"driver": "open", "rate_rps": 1.0}))
    (dst / "chipbench" / "metrics" / "dummy_rows.py").write_text(
        "def read(ctx):\n    return ctx.get('rows')\n")
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "dummy-cell", "config": "gandse-im2col",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "dummy_rows", "unit": "rows",
                               "better": "higher", "source": "program_counter",
                               "layer": "batcher", "moves": "serve_p95_ms",
                               "workloads": ["dummy-cell"]})
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location(
        "copied_harness", dst / "chipbench" / "harness.py")
    h = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(h)
    c = h.cell("dummy-cell")
    assert c["mix"]["driver"] == "open" and c["cfg"]["name"] == "gandse-im2col"
    assert [m["name"] for m in c["per_layer"]] == ["dummy_rows"]
    assert h.read_per_layer(c, {"rows": 3}) == {
        "dummy_rows": {"value": 3, "unit": "rows"}}
    assert h.read_per_layer(c, {}) == {}
    assert h.driver("open").run is not None


TOY_ORACLE = '''"""The toy design model: latency the slower of compute (OPS / PEN)
and transfer (BYTES / BW) cycles at CLOCK_HZ, infeasible where PEN
exceeds FEED x BW; power static, per PE and per word/cycle."""
import numpy as np


def evaluate(k, net, cfg):
    ops, nbytes = net[..., 0], net[..., 1]
    pen, bw = cfg[..., 0], cfg[..., 1]
    cycles = np.maximum(ops / pen, nbytes / bw)
    lat = np.where(pen <= k["FEED"] * bw, cycles / k["CLOCK_HZ"], np.inf)
    power = k["P_STATIC_W"] + k["P_PE_W"] * pen + k["P_BW_W"] * bw
    return lat, np.where(np.isfinite(lat), power, np.inf)
'''

# Runs in the copied tree, so every chipbench module is the copy's; the
# program comes from the checkout's src on PYTHONPATH.
TOY_RUN = """
import json, sys
sys.path.insert(0, sys.argv[1])
import pytest
from chipbench import harness
from chipbench.tests import tiny
assert harness.ROOT == sys.argv[1], harness.ROOT
tiny.on_cpu(pytest.MonkeyPatch())
try:
    result, checks = tiny.run(tiny.cell("toy-interactive"))
except harness.BenchError as e:
    print(json.dumps({"error": str(e)}))
else:
    print(json.dumps({"result": result, "checks": checks}))
"""


@pytest.mark.parametrize("with_oracle", [True, False],
                         ids=["oracle_file", "no_oracle_file"])
def test_new_design_model_by_files_alone(tmp_path, with_oracle):
    """A configuration of a design model the benchmark has never run joins
    it by new files and entries alone, and runs correct; without its
    oracle file the run names the file to add."""
    from chipbench.tests import toy_model
    dst = _copy(tmp_path)
    cb = dst / "chipbench"
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         "gandse-dnnweaver.json"))
    cfg.update(name="toy-space", design_model="toy",
               program_model="chipbench.tests.toy_model:ToyModel",
               net_space=toy_model.NET_SPACE,
               config_space=toy_model.CONFIG_SPACE,
               oracle_constants=toy_model.CONSTANTS)
    (cb / "configs" / "toy-space.json").write_text(json.dumps(cfg))
    if with_oracle:
        (cb / "oracles" / "toy.py").write_text(TOY_ORACLE)
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-space", "source": "test",
                             "file": "chipbench/configs/toy-space.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-interactive",
                               "config": "toy-space",
                               "traffic": "interactive-poisson", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_p95_ms":
            m["workloads"].append("toy-interactive")
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    for name in ("reference.py", "harness.py"):
        assert (cb / name).read_bytes() == \
            open(os.path.join(harness.HERE, name), "rb").read()

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", TOY_RUN, str(dst)], cwd=dst,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    if not with_oracle:
        assert "chipbench/oracles/toy.py" in out["error"], out
        return
    result, checks = out["result"], out["checks"]
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_p95_ms", "setup_s"}


def test_every_cell_resolves():
    bench = harness.benchmark()
    for w in bench["workloads"]:
        c = harness.cell(w["name"])
        harness.driver(c["mix"]["driver"])
        for m in c["per_layer"]:
            harness.reader(m["name"])
        assert c["end_to_end"], w["name"]


def test_unknown_device_kind():
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v99")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "dnnweaver-interactive", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_without_result():
    r = _run(ROOT)
    assert r.returncode == 2 and r.stdout == ""
    assert "needs a TPU" in r.stderr


def test_benchmark_files_alone_exit_without_result(tmp_path):
    r = _run(_copy(tmp_path))
    assert r.returncode != 0 and r.stdout == ""
