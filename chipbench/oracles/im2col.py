"""The im2col design model (arXiv:2208.00800, section 7.1.1): an
output-stationary accelerator running each layer as an im2col GEMM.
Latency is a roofline over three pipelined per-tile phases (load,
compute, write-back); power is static (resources) plus dynamic
(activity).  Twelve configuration dims: PEN, SDB, DSB, ISS, WSS, OSS and
the six tile sizes."""
import numpy as np


def roofline(k: dict, net, pen, dsb, sdb, iss, wss, oss,
             tic, toc, tow, toh, tkw, tkh):
    """Three-stage pipelined roofline: (latency s, power W); +inf when a
    tile does not fit its SRAM."""
    ic, oc, ow, oh, kw, kh = (net[..., i] for i in range(6))
    tic, toc = np.minimum(tic, ic), np.minimum(toc, oc)
    tow, toh = np.minimum(tow, ow), np.minimum(toh, oh)
    tkw, tkh = np.minimum(tkw, kw), np.minimum(tkh, kh)
    cd = lambda a, b: np.ceil(a / b)
    n_tiles = cd(ic, tic) * cd(oc, toc) * cd(ow, tow) * cd(oh, toh) \
        * cd(kw, tkw) * cd(kh, tkh)
    n_out_tiles = cd(oc, toc) * cd(ow, tow) * cd(oh, toh)
    tile_macs = tic * toc * tow * toh * tkw * tkh
    t_comp = cd(tile_macs, pen)
    in_words = tic * tkw * tkh * tow * toh
    w_words = tic * toc * tkw * tkh
    t_load = cd(in_words + w_words, dsb)
    out_words = toc * tow * toh
    t_store = cd(out_words, sdb)
    store_amort = t_store * (n_out_tiles / n_tiles)
    bottleneck = np.maximum(np.maximum(t_load, t_comp), store_amort)
    cycles = bottleneck * np.maximum(n_tiles - 1.0, 0.0) + t_load + t_comp \
        + t_store
    feasible = (in_words <= iss) & (w_words <= wss) & (out_words <= oss)
    cycles = np.where(feasible, cycles, np.inf)
    total_macs = ic * oc * ow * oh * kw * kh
    dram_words = n_tiles * (in_words + w_words) + n_out_tiles * out_words
    sram_words = 2.0 * total_macs + n_out_tiles * out_words
    energy = k["E_MAC_J"] * total_macs + k["E_SRAM_J"] * sram_words \
        + k["E_DRAM_J"] * dram_words
    lat = cycles / k["CLOCK_HZ"]
    p_static = (k["P_STATIC_BASE_W"] + k["P_STATIC_PE_W"] * pen
                + k["P_STATIC_SRAM_W"] * (iss + wss + oss)
                + k["P_STATIC_BW_W"] * (sdb + dsb))
    with np.errstate(invalid="ignore"):
        p_dyn = np.where(np.isfinite(lat), energy / np.maximum(lat, 1e-12),
                         0.0)
    power = np.where(feasible, p_static + p_dyn, np.inf)
    return lat, power


def evaluate(k: dict, net, cfg):
    pen, sdb, dsb, iss, wss, oss, tic, toc, tow, toh, tkw, tkh = (
        cfg[..., i] for i in range(12))
    return roofline(k, net, pen, dsb, sdb, iss, wss, oss,
                    tic, toc, tow, toh, tkw, tkh)
