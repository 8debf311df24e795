"""The comparison that decides ``correct`` fails when the timed path is
broken underneath, and when the control (the reference one precision
lower) takes the program's place.  Each test drives the rest of a run on
the CPU at a small size, with the cell's own limits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import serving, training
from chipbench.tests import tiny


@pytest.mark.parametrize("name", ["dnnweaver-interactive",
                                  "im2col-sweep-cap64k"])
def test_sound_run_is_correct(name, monkeypatch):
    tiny.on_cpu(monkeypatch)
    result, checks = tiny.run(tiny.cell(name))
    assert result["correct"], checks


def _break_select(monkeypatch, how):
    import repro.core.dse_api as api
    real = api.fused_select_batch

    def broken(model, net_idx, probs, *a, **k):
        probs = np.array(probs)
        if how == "answer":               # one winner altered where made
            sels = real(model, net_idx, probs, *a, **k)
            s = sels[0]
            if s.cfg_idx is not None:
                s.cfg_idx = s.cfg_idx.copy()
                s.cfg_idx[0] = (s.cfg_idx[0] + 1) % model.space.dims[0].n
            return sels
        half = probs.shape[0] // 2        # half the batch left out
        probs[half:] = probs[0]
        return real(model, net_idx, probs, *a, **k)

    monkeypatch.setattr(api, "fused_select_batch", broken)


@pytest.mark.parametrize("how", ["answer", "half_batch"])
def test_broken_select_is_not_correct(how, monkeypatch):
    tiny.on_cpu(monkeypatch)
    _break_select(monkeypatch, how)
    c = tiny.cell("dnnweaver-interactive")
    c["mix"].update(check_sample=40, rate_rps=400)   # batches of many rows
    result, checks = tiny.run(c)
    assert not result["correct"], checks


@pytest.mark.parametrize("name", ["dnnweaver-interactive",
                                  "im2col-sweep-cap64k"])
def test_serving_control_is_not_correct(name, monkeypatch):
    tiny.on_cpu(monkeypatch)
    c = tiny.cell(name)
    sc = serving.ServingCell(c, 5)
    fake = [serving.Answer(i, sc.req_seed(i))
            for i in range(c["mix"]["check_sample"])]
    nums = serving.compare(sc, fake, control=True)
    lim = c["mix"]["limits"]
    assert any(nums[k] > lim[k] for k in lim), (nums, lim)


def _break_epoch(monkeypatch):
    """Every step returns its state unchanged (its losses still real)."""
    import repro.core.train as T
    real = T._cached_epoch_fn

    def cached(*a, **k):
        g_opt, d_opt, epoch = real(*a, **k)

        def broken(carry, data, perm):
            copy = jax.tree.map(jnp.copy, carry)
            return carry, epoch(copy, data, perm)[1]
        return g_opt, d_opt, broken

    monkeypatch.setattr(T, "_cached_epoch_fn", cached)


def test_sound_training_is_correct(monkeypatch):
    tiny.on_cpu(monkeypatch)
    result, checks = tiny.run(tiny.cell("im2col-train"))
    assert result["correct"], checks


# Half of each batch left out is not here: at batch 1024 it reads 3-7
# times the program's own gap in every compared number, under the tenfold
# that would make it a limit's upper reading (PERF.md, Open questions).
def test_broken_training_is_not_correct(monkeypatch):
    tiny.on_cpu(monkeypatch)
    _break_epoch(monkeypatch)
    result, checks = tiny.run(tiny.cell("im2col-train"))
    assert not result["correct"], checks


def test_training_control_is_not_correct(monkeypatch):
    tiny.on_cpu(monkeypatch)
    c = tiny.cell("im2col-train")
    # at 2 x 64 the bfloat16-stored control drifts too little to show;
    # at 4 x 512 its gradients drift as at the paper's width
    c["cfg"].update(g_hidden_layers=4, g_neurons=512, d_hidden_layers=4,
                    d_neurons=512)
    tc = training.TrainCell(c, 9)
    ref = tc.reference_run()
    ctl = tc.reference_run(control=True)
    nums, _ = training.numbers(ctl, ref, c["mix"]["compare_steps"])
    lim = c["mix"]["limits"]
    assert any(nums[k] > lim[k] for k in lim), (nums, lim)
