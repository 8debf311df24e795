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
