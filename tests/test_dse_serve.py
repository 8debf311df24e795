"""`repro.launch.dse_serve`'s exit code: zero only when every request was
answered on the device route.

The server's degraded host route returns the same Selections as the
device route, so a run whose device route kept failing would look healthy
from its responses alone; `repro.launch.dse_serve` must report it.
"""
import pytest

from repro.launch import dse_serve
from repro.serve import FaultPlan, FaultyEngine

ARGV = ["--model", "dnnweaver", "--requests", "8", "--max-batch", "4",
        "--data", "128", "--layers", "1", "--neurons", "16"]


@pytest.fixture(autouse=True)
def no_checkout_cache(monkeypatch):
    # the launcher's persistent compile cache is for real runs, not tests
    monkeypatch.setattr(dse_serve, "use_compile_cache", lambda: "")


@pytest.mark.parametrize("mode", [[], ["--concurrent"]])
def test_healthy_run_exits_zero(mode):
    assert dse_serve.main(ARGV + mode) == 0


@pytest.mark.parametrize("device_route_only", [True, False],
                         ids=["degraded", "failed"])
def test_persistent_dispatch_fault_exits_nonzero(monkeypatch, capsys,
                                                 device_route_only):
    """Every dispatch after the warmup raises: on the device route only
    (the server falls back to the degraded host route and still answers)
    or on both routes (requests FAIL).  Either run must exit nonzero."""
    engines, gandse = [], dse_serve.GANDSE

    def faulty_gandse(*a, **kw):
        # fault-eligible dispatch 0 is the warmup batch; all later ones raise
        engines.append(FaultyEngine(
            gandse(*a, **kw),
            FaultPlan(burst_start=1, burst_len=10 ** 9,
                      device_route_only=device_route_only)))
        return engines[-1]

    monkeypatch.setattr(dse_serve, "GANDSE", faulty_gandse)
    assert dse_serve.main(ARGV + ["--concurrent"]) != 0
    assert engines[0].injected_errors > 0
    err = capsys.readouterr().err
    assert "FAIL" in err
    assert ("degraded" in err) if device_route_only else ("FAILED" in err)
