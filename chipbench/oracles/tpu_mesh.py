"""The TPU-mesh design model: one LLM training job's layout on a TPU mesh,
GANDSE pointed at the parallelism knobs instead of an FPGA template.

Twenty network dims describe the model and the job: LAYERS, DENSE
(leading dense layers), MTP (prediction modules), DMODEL, DFF, EXPERTS
(routed, 0 = dense), TOPK, SHARED, EFF (expert width), HEADS, QLORA,
KVLORA, DNOPE, DROPE, DV (multi-head latent attention; QLORA or KVLORA 0
= full rank), VOCAB, TIED, WINDOW (0 = attention products not counted),
SEQ, GBATCH.  Nine configuration dims: REPLICAS (over DCN), PP, DP, TP,
EP (drawn from a stage's DP x TP chips), MICRO, REMAT, BYTES_P, COMPRESS.

Latency is the step time: the largest of a compute, an HBM and a
collective term, times the pipeline bubble (MICRO + PP - 1) / MICRO.
Power is chips x (idle + dynamic x compute share).  Parameters count
latent attention, SwiGLU FFNs, (EXPERTS + SHARED) experts a MoE layer
(TOPK + SHARED activated), routers, the MTP modules and an untied head;
experts are gathered only over the chips of one EP group, and the
tokens' dispatch and combine is an all-to-all of (EP - 1) / EP of them.
"""
import numpy as np


def _params(net):
    (layers, dense, mtp, d, dff, e, topk, shared, f, h, q, kv, dn, dr, dv,
     vocab, tied) = (net[..., i] for i in range(17))
    attn_q = np.where(q > 0, d * q + q * h * (dn + dr), d * h * (dn + dr))
    attn_kv = np.where(kv > 0, d * (kv + dr) + kv * h * (dn + dv),
                       d * h * (dn + dr) + d * h * dv)
    attn = attn_q + attn_kv + h * dv * d
    ffn = 3.0 * d * dff
    expert = 3.0 * d * f
    moe = e > 0
    moe_layers = np.where(moe, layers - dense, 0.0)
    dense_layers = layers - moe_layers
    moe_total = (e + shared) * expert + d * e
    moe_act = (topk + shared) * expert + d * e
    head = vocab * d
    n_total = (layers * attn + dense_layers * ffn + moe_layers * moe_total
               + mtp * (attn + np.where(moe, moe_total, ffn) + 2.0 * d * d)
               + head + (1.0 - tied) * head)
    n_act = (layers * attn + dense_layers * ffn + moe_layers * moe_act
             + mtp * (attn + np.where(moe, moe_act, ffn) + 2.0 * d * d + head)
             + head)
    n_moe = moe_layers + np.where(moe, mtp, 0.0)
    n_exp = n_moe * e * expert
    return n_total, n_act, n_exp, n_moe


def evaluate(k: dict, net, cfg):
    layers, mtp, d, e, topk, shared, f, h = (
        net[..., i] for i in (0, 2, 3, 5, 6, 7, 8, 9))
    q, kv, dn, dr, dv, window, seq, gb = (
        net[..., i] for i in (10, 11, 12, 13, 14, 17, 18, 19))
    reps, pp, dp, tp, ep, micro, remat, bp, comp = (
        cfg[..., i] for i in range(9))
    n_total, n_act, n_exp, n_moe = _params(net)
    stage = dp * tp
    chips = reps * (pp * stage)
    tokens = gb * seq

    ok = (stage <= k["CHIPS_PER_POD"]) & (gb % (reps * dp * micro) == 0) \
        & (h % tp == 0) & (ep <= stage) & (e % ep == 0)

    w = np.minimum(window, seq)
    keys = w - w * w / (2.0 * seq)
    attn_flops = 6.0 * h * (dn + dr + dv) * keys * tokens * (layers + mtp)
    flops = (6.0 * n_act * tokens + attn_flops) * (1.0 + 0.33 * remat)
    t_comp = flops / (chips * k["PEAK_FLOPS"])

    state = n_total * (bp + 8.0) / (pp * stage)
    stage_layers = (layers + mtp) / pp
    held = stage_layers * np.minimum(pp, micro)
    rows = gb / (reps * dp * micro)
    act = rows * seq * d * 2.0 * held / tp
    extra = rows * seq * 2.0 * (q + kv + dr + n_moe / (layers + mtp)
                                * (topk + shared) * 2.0 * f) * held / tp
    act = np.where(remat > 0, act, act * 6.0 + extra)
    ok &= state + act <= k["HBM_CAP"]
    t_mem = (micro * 3.0 * n_total * bp / (pp * stage) + 6.0 * act) \
        / k["HBM_BW"]

    act_chip = gb / np.maximum(reps * dp * micro, 1.0) * seq * d * 2.0
    tp_b = np.where(tp > 1, stage_layers * 4.0 * 2.0 * 2.0 * act_chip * micro,
                    0.0)
    n_dense = n_total - n_exp
    ag = np.where(dp > 1, micro * 2.0 * n_dense * bp / (pp * tp), 0.0)
    gr = np.where(dp > 1, 2.0 * n_dense * bp / (pp * tp), 0.0)
    group = stage / ep
    exp_ag = np.where(group > 1, micro * 2.0 * n_exp * bp / (pp * ep), 0.0)
    exp_gr = np.where(group > 1, 2.0 * n_exp * bp / (pp * ep), 0.0)
    a2a = 4.0 * (tokens / (reps * dp * tp)) * topk * d * bp * (ep - 1.0) \
        / ep * (n_moe / pp)
    sends = np.where(pp > 1, 2.0 * (gb / (reps * dp)) * seq * d * 2.0 / tp,
                     0.0)
    over_dcn = pp * stage > k["CHIPS_PER_POD"]
    t_ici = (tp_b + ag + gr + exp_ag + exp_gr + a2a
             + np.where(over_dcn, 0.0, sends)) / k["ICI_LINK_BW"]
    dcn = np.where(reps > 1, 2.0 * n_total * bp / comp / (pp * stage), 0.0)
    t_dcn = (dcn + np.where(over_dcn, sends, 0.0)) / k["DCN_BW"]
    t_coll = t_ici + t_dcn

    lat = np.maximum(np.maximum(t_comp, t_mem), t_coll) \
        * ((micro + pp - 1.0) / micro)
    util = np.where(lat > 0, t_comp / np.maximum(lat, 1e-12), 0.0)
    power = chips * (k["CHIP_IDLE_W"] + k["CHIP_DYN_W"] * util)
    return np.where(ok, lat, np.inf), np.where(ok, power, np.inf)
