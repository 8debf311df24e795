"""The DNNWeaver design model (arXiv:2208.00800, section 7.1.1): a
systolic-array template whose four configuration dims are PEN, ISS, WSS
and OSS.  The template picks its own tiles, and its DRAM bandwidths are
fixed board properties (``FIXED_DSB``, ``FIXED_SDB``); latency and power
are the im2col roofline's on those tiles."""
import numpy as np

from chipbench.oracles.im2col import roofline


def _pow2floor(x):
    return np.power(2.0, np.floor(np.log2(np.maximum(x, 1.0))))


def _dnnweaver_tiles(net, iss, wss, oss):
    """DNNWeaver's own greedy schedule: full kernel window, channels fit
    the weight SRAM, a square-ish output plane fits the output SRAM, then
    halvings until the im2col patch fits the input SRAM."""
    ic, oc, ow, oh, kw, kh = (net[..., i] for i in range(6))
    tkw, tkh = kw, kh
    tic = np.maximum(_pow2floor(np.minimum(ic, wss / np.maximum(kw * kh,
                                                                1.0))), 1.0)
    toc = np.maximum(_pow2floor(np.minimum(
        np.minimum(oc, oss), wss / np.maximum(tic * kw * kh, 1.0))), 1.0)
    plane_cap = np.maximum(oss / np.maximum(toc, 1.0), 1.0)
    tow = np.maximum(np.minimum(_pow2floor(np.sqrt(plane_cap)), ow), 1.0)
    toh = np.maximum(np.minimum(_pow2floor(plane_cap / tow), oh), 1.0)
    tiles = [toh, tow, tic]
    for j in range(3):
        patch = tiles[2] * tkw * tkh * tiles[1] * tiles[0]
        excess = np.power(2.0, np.ceil(np.log2(
            np.maximum(patch / np.maximum(iss, 1.0), 1.0))))
        f = np.minimum(tiles[j], excess)
        tiles[j] = np.maximum(tiles[j] / f, 1.0)
    toh, tow, tic = tiles
    return tic, toc, tow, toh, tkw, tkh


def evaluate(k: dict, net, cfg):
    pen, iss, wss, oss = (cfg[..., i] for i in range(4))
    tiles = _dnnweaver_tiles(net, iss, wss, oss)
    return roofline(k, net, pen, k["FIXED_DSB"], k["FIXED_SDB"], iss, wss,
                    oss, *tiles)
