"""Design-model invariants (property-based).

The paper's models are calibrated against RTL simulation; ours are stated
analytic constants, so the tests check *physics-shaped* invariants rather
than absolute numbers.
"""
import json
import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal CI image — seeded-random fallback
    from _mini_hypothesis import given, settings, strategies as st

from repro.design_models.dnnweaver import DnnWeaverModel
from repro.design_models.im2col import Im2colModel
from repro.design_models.tpu_mesh import (DEEPSEEK_V3, HBM_CAP, NET_DIMS,
                                          DeepSeekV3Mesh, TpuMeshModel,
                                          make_mesh_space, param_counts,
                                          roofline_terms)


@pytest.fixture(scope="module")
def im2col():
    return Im2colModel()


@pytest.fixture(scope="module")
def dnnw():
    return DnnWeaverModel()


def _sample(model, seed, n=64):
    rng = np.random.default_rng(seed)
    return (model.net_space.sample_indices(rng, n),
            model.space.sample_indices(rng, n))


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_im2col_more_pes_never_slower(seed):
    model = Im2colModel()
    net_idx, cfg_idx = _sample(model, seed)
    lo = cfg_idx.copy()
    hi = cfg_idx.copy()
    lo[:, 0] = 0                       # min PEN
    hi[:, 0] = model.space.dims[0].n - 1  # max PEN
    lat_lo, _ = model.evaluate_indices(net_idx, lo)
    lat_hi, _ = model.evaluate_indices(net_idx, hi)
    ok = np.isfinite(lat_lo) & np.isfinite(lat_hi)
    assert np.all(lat_hi[ok] <= lat_lo[ok] * (1 + 1e-9))


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_im2col_more_sram_more_static_power_when_feasible(seed):
    model = Im2colModel()
    net_idx, cfg_idx = _sample(model, seed)
    lo = cfg_idx.copy(); hi = cfg_idx.copy()
    for d in (3, 4, 5):                # ISS, WSS, OSS
        lo[:, d] = np.minimum(lo[:, d], hi[:, d])
        hi[:, d] = model.space.dims[d].n - 1
    lat_lo, p_lo = model.evaluate_indices(net_idx, lo)
    lat_hi, p_hi = model.evaluate_indices(net_idx, hi)
    # same latency rows (tiling unchanged): bigger SRAM costs static power
    ok = np.isfinite(p_lo) & np.isfinite(p_hi) & np.isclose(lat_lo, lat_hi)
    assert np.all(p_hi[ok] >= p_lo[ok] - 1e-9)


def test_im2col_feasibility_infeasible_tile_is_inf(im2col):
    """A tile bigger than every SRAM must be rejected."""
    net = np.array([[256., 256., 64., 64., 5., 5.]])
    cfg = np.array([[4096., 512., 512., 256., 256., 256.,
                     128., 128., 256., 256., 5., 5.]])
    lat, p = im2col.evaluate(net, cfg)
    assert np.isinf(lat[0]) and np.isinf(p[0])


def test_dnnweaver_derived_tiles_always_fit(dnnw):
    rng = np.random.default_rng(0)
    net_idx = dnnw.net_space.sample_indices(rng, 256)
    cfg_idx = dnnw.space.sample_indices(rng, 256)
    net = dnnw.net_space.values_from_indices(net_idx)
    cfg = dnnw.space.values_from_indices(cfg_idx)
    pen, iss, wss, oss = (cfg[..., i] for i in range(4))
    tic, toc, tow, toh, tkw, tkh = dnnw._derive_tiles(net, iss, wss, oss)
    kw, kh = net[..., 4], net[..., 5]
    assert np.all(tic * tkw * tkh * tow * toh <= iss * (1 + 1e-9))
    assert np.all(toc * tow * toh <= oss * (1 + 1e-9))


def test_bigger_network_never_faster(im2col):
    """Scaling every net dim up cannot reduce latency at a fixed config."""
    rng = np.random.default_rng(3)
    cfg_idx = im2col.space.sample_indices(rng, 128)
    small = np.zeros((128, 6), np.int64)
    big = np.stack([np.full(128, d.n - 1) for d in im2col.net_space.dims], -1)
    lat_s, _ = im2col.evaluate_indices(small, cfg_idx)
    lat_b, _ = im2col.evaluate_indices(big, cfg_idx)
    ok = np.isfinite(lat_s) & np.isfinite(lat_b)
    assert np.all(lat_b[ok] >= lat_s[ok])


# ---------------------------------------------------------------------------
# TPU-mesh model (beyond-paper)
# ---------------------------------------------------------------------------
DATA = os.path.join(os.path.dirname(__file__), "data")


def _job(**over):
    """A dense job's 20-value descriptor (32 heads of d/32, full-rank
    attention, tied head, attention products not counted), with
    ``over`` set."""
    d = float(over.get("DMODEL", 2048))
    job = dict(LAYERS=24, DENSE=0, MTP=0, DMODEL=d, DFF=4 * d, EXPERTS=0,
               TOPK=1, SHARED=0, EFF=1024, HEADS=32, QLORA=0, KVLORA=0,
               DNOPE=d / 32, DROPE=0, DV=d / 32, VOCAB=65536, TIED=1,
               WINDOW=0, SEQ=4096, GBATCH=256)
    job.update(over)
    return np.array([[float(job[n]) for n in NET_DIMS]])


def _dsv3(**over):
    job = {**DEEPSEEK_V3, "SEQ": 4096, "GBATCH": 1920, **over}
    return np.array([[float(job[n]) for n in NET_DIMS]])


def _layout(**over):
    c = dict(REPLICAS=1, PP=1, DP=8, TP=4, EP=1, MICRO=4, REMAT=1, BYTES_P=2,
             COMPRESS=1)
    c.update(over)
    return np.array([[float(c[d.name]) for d in make_mesh_space().dims]])


def test_tpu_mesh_more_chips_not_slower_when_feasible():
    model = TpuMeshModel()
    net = _job(LAYERS=24, DMODEL=2048, GBATCH=256)
    lat_b, _ = model.evaluate(net, _layout(DP=8))      # 32 chips
    lat_w, _ = model.evaluate(net, _layout(DP=16))     # 64 chips
    assert np.isfinite(lat_b[0]) and np.isfinite(lat_w[0])
    assert lat_w[0] <= lat_b[0] * (1 + 1e-9)


def test_tpu_mesh_infeasible_hbm_is_inf():
    model = DeepSeekV3Mesh()
    one_chip = _layout(DP=1, TP=1, MICRO=1, REMAT=0, BYTES_P=4)
    lat, p = model.evaluate(_dsv3(), one_chip)           # 683B params
    assert np.isinf(lat[0]) and np.isinf(p[0])
    t = roofline_terms(_dsv3(), one_chip)
    assert t["hbm"][0] > HBM_CAP


def test_tpu_mesh_compression_helps_multipod_collective():
    model = TpuMeshModel()
    net = _job(LAYERS=48, DMODEL=4096, GBATCH=512, VOCAB=131072)
    nocomp = _layout(REPLICAS=2, DP=16, TP=16, MICRO=1, COMPRESS=1)
    comp = _layout(REPLICAS=2, DP=16, TP=16, MICRO=1, COMPRESS=4)
    lat_n, _ = model.evaluate(net, nocomp)
    lat_c, _ = model.evaluate(net, comp)
    assert np.isfinite(lat_n[0])
    assert lat_c[0] <= lat_n[0] * (1 + 1e-9)


def test_tpu_mesh_dense_case_matches_recorded_values():
    """The dense case (no experts, full-rank attention with d/H-wide heads,
    tied head, no attention products, one stage, EP 1) gives, bit for
    bit, the latency and power that the earlier six-dim model
    {LAYERS, DMODEL, DFF_MULT, SEQ, GBATCH, VOCAB} x {PODS, DP, TP, MICRO,
    REMAT, BYTES_P, COMPRESS} gave at 144 points (32 of them infeasible),
    recorded from it into the data file."""
    with open(os.path.join(DATA, "tpu_mesh_dense_parent.json")) as f:
        rec = json.load(f)
    assert rec["net_dims"] == ["LAYERS", "DMODEL", "DFF_MULT", "SEQ",
                               "GBATCH", "VOCAB"]
    nets, cfgs, lat, pw = [], [], [], []
    for pt in rec["points"]:
        layers, d, mult, seq, gb, vocab = pt["net"]
        pods, dp, tp, micro, remat, bp, comp = pt["config"]
        nets.append(_job(LAYERS=layers, DMODEL=d, DFF=mult * d, SEQ=seq,
                         GBATCH=gb, VOCAB=vocab)[0])
        cfgs.append(_layout(REPLICAS=pods, DP=dp, TP=tp, MICRO=micro,
                            REMAT=remat, BYTES_P=bp, COMPRESS=comp)[0])
        lat.append(pt["latency"])
        pw.append(pt["power"])
    assert len(nets) >= 64 and np.isinf(lat).sum() >= 16
    got_l, got_p = TpuMeshModel().evaluate(np.array(nets), np.array(cfgs))
    np.testing.assert_array_equal(got_l, np.array(lat))
    np.testing.assert_array_equal(got_p, np.array(pw))


def test_tpu_mesh_dsv3_parameter_counts():
    """The published 671B parameters (MTP module excluded) and 37B
    activated a token (arXiv:2412.19437)."""
    total, act, n_exp, n_moe = param_counts(_dsv3(MTP=0))
    assert abs(total[0] / 671e9 - 1) <= 0.005
    assert abs(act[0] / 37e9 - 1) <= 0.03
    assert n_moe[0] == 58 and n_exp[0] == 58 * 256 * 3 * 7168 * 2048
    # the MTP module adds one attention, one MoE layer and a 2d x d
    # projection, and reuses the embedding and head
    with_mtp, _, _, _ = param_counts(_dsv3())
    assert 11e9 < with_mtp[0] - total[0] < 12e9


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_tpu_mesh_expert_parallel_terms(seed):
    """Over the chips of a stage, more EP ranks gather fewer expert bytes
    (strictly, while an EP group spans more than one chip) and the token
    all-to-all grows as (EP - 1) / EP; EP 1 exchanges nothing."""
    rng = np.random.default_rng(seed)
    dp, tp = (float(2 ** rng.integers(3, 7)), float(2 ** rng.integers(0, 5)))
    base = dict(REPLICAS=float(2 ** rng.integers(0, 4)),
                PP=float(2 ** rng.integers(0, 5)), DP=dp, TP=tp,
                MICRO=float(2 ** rng.integers(0, 6)))
    eps = [e for e in (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0)
           if e <= dp * tp]
    t = [roofline_terms(_dsv3(), _layout(EP=e, **base)) for e in eps]
    ag = [x["exp_ag_bytes"][0] for x in t]
    a2a = [x["a2a_bytes"][0] for x in t]
    assert a2a[0] == 0.0 and ag[0] > 0
    for k in range(1, len(eps)):
        if dp * tp / eps[k] > 1:
            assert ag[k] < ag[k - 1]
        assert a2a[k] == pytest.approx(
            a2a[1] * ((eps[k] - 1) / eps[k]) / 0.5, rel=1e-12)
    # the rest of the step does not see EP
    for x in t[1:]:
        assert x["ag_bytes"][0] == t[0]["ag_bytes"][0]
        assert x["t_comp"][0] == t[0]["t_comp"][0]


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_tpu_mesh_more_microbatches_shrink_the_bubble(seed):
    rng = np.random.default_rng(seed)
    pp = float(2 ** rng.integers(1, 5))
    micros = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0)
    bub = [roofline_terms(_dsv3(), _layout(PP=pp, MICRO=m))["bubble"][0]
           for m in micros]
    assert all(b1 < b0 for b0, b1 in zip(bub, bub[1:]))
    assert bub[0] == pp                            # (1 + PP - 1) / 1
    one = roofline_terms(_dsv3(), _layout(PP=1.0, MICRO=micros[-1]))
    assert one["bubble"][0] == 1.0


def test_tpu_mesh_experts_not_divisible_by_ep_infeasible():
    """160 routed experts (DeepSeek-V2's count) over 32 EP ranks run; over
    64 they do not divide, and the layout is infeasible."""
    model = TpuMeshModel()
    net = _dsv3(EXPERTS=160)
    layout = dict(REPLICAS=1, PP=4, DP=8, TP=16, MICRO=8, REMAT=1)
    lat, pw = model.evaluate(net, _layout(EP=32, **layout))
    assert np.isfinite(lat[0]) and np.isfinite(pw[0])
    lat, pw = model.evaluate(net, _layout(EP=64, **layout))
    assert np.isinf(lat[0]) and np.isinf(pw[0])
    # EP may not exceed the stage's chips either
    lat, _ = model.evaluate(_dsv3(), _layout(EP=64, **{**layout, "DP": 2}))
    assert np.isinf(lat[0])
