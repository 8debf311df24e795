"""TPU-mesh design model — the beyond-paper GANDSE application.

The paper's GAN-DSE engine searches *FPGA accelerator* configurations
against an analytic latency/power model.  Here the same engine is pointed
at the layout of an LLM training job on a TPU mesh: the "network
parameters" describe the model and the job (dense or sparse-expert
layers, multi-head or latent attention, sequence length, global batch),
and the "configurations" are the parallelism knobs (replicas over DCN,
pipeline stages, data / tensor / expert axes, microbatches, remat, dtype,
gradient compression).

Objectives (the paper's "latency <= x, power <= y" format):
  latency = roofline-bounded training step time (s)
  power   = cluster board power (W): chips * (idle + dynamic * utilization)

The roofline, term by term (net dims in capitals as in
``make_workload_space``, config dims as in ``make_mesh_space``; d =
DMODEL, H = HEADS, q = QLORA, kv = KVLORA, dn, dr, dv = DNOPE, DROPE, DV,
E = EXPERTS, f = EFF, V = VOCAB; every test below is elementwise):

  parameters (``param_counts``)
    attention    q-side  d*q + q*H*(dn+dr)            (q > 0: latent)
                         d*H*(dn+dr)                  (q = 0)
                 kv-side d*(kv+dr) + kv*H*(dn+dv)     (kv > 0: latent)
                         d*H*(dn+dr) + d*H*dv         (kv = 0)
                 out     H*dv*d
    FFN          dense layer 3*d*DFF; expert 3*d*f (SwiGLU)
    MoE layer    (E + SHARED) experts + router d*E; total (E + SHARED),
                 activated (TOPK + SHARED) experts + router
    layers       LAYERS - DENSE MoE layers when E > 0, else none
    MTP          MTP modules, each attention + one MoE layer (a dense FFN
                 when E = 0) + a 2d*d projection; they reuse the head
    embedding    V*d; head V*d unless TIED
    N_total      all of the above;  N_exp = routed experts' parameters
    N_act        per token: attention + dense FFNs + activated experts
                 + routers + MTP + the head (V*d); the embedding is a
                 lookup and costs no FLOPs

  chips_stage = DP*TP;  chips = REPLICAS*PP*DP*TP;  tokens = GBATCH*SEQ
  feasible    DP*TP <= CHIPS_PER_POD, EP <= DP*TP, E % EP == 0,
              H % TP == 0, GBATCH % (REPLICAS*DP*MICRO) == 0,
              HBM per chip <= HBM_CAP

  compute     keys  = w - w^2 / (2*SEQ), w = min(WINDOW, SEQ)
                      (mean keys a query attends to; WINDOW 0: no
                      attention products counted)
              flops = (6*N_act*tokens + 6*H*(dn+dr+dv)*keys*tokens
                       *(LAYERS+MTP)) * (1 + 0.33*REMAT)
              t_comp = flops / (chips * PEAK_FLOPS)
  memory      state = N_total*(BYTES_P+8) / (PP*chips_stage)
              rows  = GBATCH / (REPLICAS*DP*MICRO)   (rows of a microbatch)
              act   = rows*SEQ*d*2 * ((LAYERS+MTP)/PP * min(PP, MICRO)) / TP
              without remat: act*6 + rows*SEQ*2*(q + kv + dr
                     + moe_share*(TOPK+SHARED)*2*f) * (same layer factor)/TP,
                     moe_share = MoE layers / (LAYERS+MTP)
              HBM per chip = state + act
              t_mem = (MICRO*3*N_total*BYTES_P/(PP*chips_stage) + 6*act)
                      / HBM_BW
  collectives (bytes a chip moves per step)
              TP      4 all-reduces a layer, fwd+bwd, every microbatch:
                      (LAYERS+MTP)/PP * 16 * rows*SEQ*d*2 * MICRO  (TP > 1)
              FSDP    all-gather per microbatch + gradient reduce-scatter
                      of the non-expert parameters over DP:
                      (MICRO*2 + 2) * (N_total-N_exp)*BYTES_P/(PP*TP)  (DP > 1)
              experts the same two over the chips of one EP group only
                      (DP*TP/EP > 1): (MICRO*2 + 2) * N_exp*BYTES_P/(PP*EP);
                      EP = 1 gathers every expert on every chip
              all-to-all  dispatch and combine, fwd and bwd, per MoE layer
                      of the stage: 4*tokens_chip*TOPK*d*BYTES_P*(EP-1)/EP,
                      tokens_chip = tokens / (REPLICAS*DP*TP)
              stage sends  PP > 1: 2 * GBATCH/(REPLICAS*DP)*SEQ*d*2 / TP,
                      over DCN once a replica spans more than CHIPS_PER_POD
                      chips (PP*DP*TP > CHIPS_PER_POD), else over ICI
              DCN     REPLICAS > 1: 2*N_total*BYTES_P/COMPRESS/(PP*chips_stage)
              t_coll = ICI bytes / ICI_LINK_BW + DCN bytes / DCN_BW
  step        latency = max(t_comp, t_mem, t_coll) * (MICRO+PP-1)/MICRO
              (the pipeline bubble)
              util  = t_comp / latency
              power = chips * (CHIP_IDLE_W + CHIP_DYN_W * util)

Infeasible layouts give latency = power = +inf.  In the dense case
(EXPERTS = 0, QLORA = KVLORA = DROPE = 0, DNOPE = DV = d/H, TIED = 1,
WINDOW = 0, MTP = 0, PP = EP = 1, DFF = mult*d) every new term is exactly
zero or one and the model gives the numbers of the earlier six-dim
descriptor {LAYERS, DMODEL, DFF_MULT, SEQ, GBATCH, VOCAB} bit for bit
(``tests/test_design_models.py`` holds it to values recorded from it).
``chipbench/oracles/tpu_mesh.py`` is the benchmark's float64 reference of
the same equations, in the same operation order.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.core.encoding import ConfigSpace
from repro.design_models.base import DesignModel, make_dim, pow2_choices
from repro.utils.roofline import HBM_BW, ICI_LINK_BW, PEAK_FLOPS_BF16

DCN_BW = 25e9            # B/s cross-pod per chip
HBM_CAP = 16e9           # bytes per chip (v5e-class)
CHIP_IDLE_W = 150.0
CHIP_DYN_W = 250.0
CHIPS_PER_POD = 256

NET_DIMS = ("LAYERS", "DENSE", "MTP", "DMODEL", "DFF", "EXPERTS", "TOPK",
            "SHARED", "EFF", "HEADS", "QLORA", "KVLORA", "DNOPE", "DROPE",
            "DV", "VOCAB", "TIED", "WINDOW", "SEQ", "GBATCH")


def make_workload_space() -> ConfigSpace:
    """Net-parameter space: dense and sparse-expert decoders, multi-head
    and latent attention, tied and untied heads, and the job's sequence
    length and global batch."""
    grid = {
        "LAYERS": (24, 32, 40, 48, 61, 80),
        "DENSE": (0, 1, 3),              # leading dense layers (MoE only)
        "MTP": (0, 1),                   # multi-token prediction modules
        "DMODEL": (2048, 4096, 5120, 7168, 8192),
        "DFF": (8192, 14336, 18432, 28672),
        "EXPERTS": (0, 8, 64, 256),      # routed experts; 0 = dense
        "TOPK": (1, 2, 8),
        "SHARED": (0, 1, 2),
        "EFF": (1024, 2048, 4096),       # expert FFN width
        "HEADS": (16, 32, 64, 128),
        "QLORA": (0, 1536),              # 0 = full-rank query
        "KVLORA": (0, 512),              # 0 = full-rank keys and values
        "DNOPE": (64, 128),
        "DROPE": (0, 64),
        "DV": (64, 128),
        "VOCAB": (32768, 65536, 129280, 151936, 262144),
        "TIED": (0, 1),
        "WINDOW": (0, 4096, 131072),     # 0 = attention products not counted
        "SEQ": (2048, 4096, 8192, 32768, 131072),
        "GBATCH": (64, 256, 480, 1920, 3072),
    }
    return ConfigSpace(dims=tuple(make_dim(n, grid[n]) for n in NET_DIMS))


def make_mesh_space() -> ConfigSpace:
    """Configuration space: the parallelism knobs (235,200 layouts)."""
    return ConfigSpace(dims=(
        make_dim("REPLICAS", (1, 2, 4, 8)),           # pipeline copies, DCN
        make_dim("PP", pow2_choices(1, 16)),          # pipeline stages
        make_dim("DP", pow2_choices(1, 64)),          # per-stage data axis
        make_dim("TP", pow2_choices(1, 16)),          # per-stage model axis
        make_dim("EP", pow2_choices(1, 64)),          # expert axis in DP*TP
        make_dim("MICRO", pow2_choices(1, 32)),       # grad-accum microbatches
        make_dim("REMAT", (0, 1)),
        make_dim("BYTES_P", (2, 4)),                  # param dtype
        make_dim("COMPRESS", (1, 4)),                 # DCN grad compression x
    ))


def param_counts(net, xp=np):
    """(N_total, N_act, N_exp, MoE layers) of a net descriptor (the
    module docstring's parameter equations)."""
    (layers, dense, mtp, dm, dff, experts, topk, shared, eff, heads, qlora,
     kvlora, dnope, drope, dv, vocab, tied, _w, _s, _b) = (
        net[..., i] for i in range(len(NET_DIMS)))
    attn_q = xp.where(qlora > 0, dm * qlora + qlora * heads * (dnope + drope),
                      dm * heads * (dnope + drope))
    attn_kv = xp.where(kvlora > 0,
                       dm * (kvlora + drope) + kvlora * heads * (dnope + dv),
                       dm * heads * (dnope + drope) + dm * heads * dv)
    attn = attn_q + attn_kv + heads * dv * dm
    ffn = 3.0 * dm * dff
    expert = 3.0 * dm * eff
    moe = experts > 0
    moe_layers = xp.where(moe, layers - dense, 0.0)
    dense_layers = layers - moe_layers
    moe_total = (experts + shared) * expert + dm * experts
    moe_act = (topk + shared) * expert + dm * experts
    mtp_ffn_total = xp.where(moe, moe_total, ffn)
    mtp_ffn_act = xp.where(moe, moe_act, ffn)
    head = vocab * dm
    n_total = (layers * attn + dense_layers * ffn + moe_layers * moe_total
               + mtp * (attn + mtp_ffn_total + 2.0 * dm * dm)
               + head + (1.0 - tied) * head)
    n_act = (layers * attn + dense_layers * ffn + moe_layers * moe_act
             + mtp * (attn + mtp_ffn_act + 2.0 * dm * dm + head) + head)
    n_exp = (moe_layers + xp.where(moe, mtp, 0.0)) * experts * expert
    return n_total, n_act, n_exp, moe_layers + xp.where(moe, mtp, 0.0)


def roofline_terms(net, c, xp=np) -> Dict[str, object]:
    """The module docstring's roofline: latency and power (+inf where the
    layout is infeasible), and the terms the tests pin by name."""
    (layers, dense, mtp, dm, dff, experts, topk, shared, eff, heads, qlora,
     kvlora, dnope, drope, dv, vocab, tied, window, seq, gb) = (
        net[..., i] for i in range(len(NET_DIMS)))
    reps, pp, dp, tp, ep, micro, remat, bytes_p, comp = (
        c[..., i] for i in range(9))

    n_total, n_act, n_exp, n_moe = param_counts(net, xp)
    chips_per_pod = dp * tp                   # one pipeline stage
    chips = reps * (pp * chips_per_pod)
    tokens = gb * seq

    # --- feasibility --------------------------------------------------------
    feasible = (chips_per_pod <= CHIPS_PER_POD) \
        & (gb % (reps * dp * micro) == 0) & (heads % tp == 0) \
        & (ep <= chips_per_pod) & (experts % ep == 0)

    # --- compute term -------------------------------------------------------
    w = xp.minimum(window, seq)
    keys = w - w * w / (2.0 * seq)
    attn_flops = 6.0 * heads * (dnope + drope + dv) * keys * tokens \
        * (layers + mtp)
    flops = (6.0 * n_act * tokens + attn_flops) * (1.0 + 0.33 * remat)
    t_comp = flops / (chips * PEAK_FLOPS_BF16)

    # --- memory term --------------------------------------------------------
    # params+opt per chip (FSDP over dp*tp within a stage)
    state_bytes = n_total * (bytes_p + 8.0) / (pp * chips_per_pod)
    stage_layers = (layers + mtp) / pp
    held = stage_layers * xp.minimum(pp, micro)   # layers x microbatches held
    act_rows = gb / (reps * dp * micro)               # rows resident
    act_bytes = act_rows * seq * dm * 2.0 * held / tp
    moe_share = n_moe / (layers + mtp)
    act_extra = act_rows * seq * 2.0 * (
        qlora + kvlora + drope + moe_share * (topk + shared) * 2.0 * eff) \
        * held / tp
    act_bytes = xp.where(remat > 0, act_bytes, act_bytes * 6.0 + act_extra)
    hbm = state_bytes + act_bytes
    feasible &= hbm <= HBM_CAP
    # traffic: weights streamed once per microbatch (+bwd), acts 3x
    traffic = (micro * 3.0 * n_total * bytes_p / (pp * chips_per_pod)
               + 6.0 * act_bytes)
    t_mem = traffic / HBM_BW

    # --- collective term ----------------------------------------------------
    # Per-CHIP bytes (ring collectives move ~2x the local shard per chip
    # regardless of group size — calibrated against the compiled-HLO
    # roofline of the 16x16 and 4x64 validation runs, see
    # benchmarks/bench_gan_hillclimb.py + EXPERIMENTS.md §Perf C).
    rows_per_chip = gb / xp.maximum(reps * dp * micro, 1.0)
    act_bytes_chip = rows_per_chip * seq * dm * 2.0
    # 4 TP all-reduces per layer, fwd+bwd, every microbatch
    tp_bytes = xp.where(tp > 1,
                        stage_layers * 4.0 * 2.0 * 2.0 * act_bytes_chip * micro,
                        0.0)
    n_dense = n_total - n_exp
    # FSDP all-gather of params each microbatch (fwd+bwd) over dp:
    # each chip receives ~ params/tp per gather
    ag_bytes = xp.where(dp > 1,
                        micro * 2.0 * n_dense * bytes_p / (pp * tp), 0.0)
    # gradient reduce-scatter/all-gather over dp (ICI)
    gr_bytes = xp.where(dp > 1, 2.0 * n_dense * bytes_p / (pp * tp), 0.0)
    # routed experts: the same gather and reduction, over one EP group
    ep_group = chips_per_pod / ep
    exp_ag_bytes = xp.where(ep_group > 1,
                            micro * 2.0 * n_exp * bytes_p / (pp * ep), 0.0)
    exp_gr_bytes = xp.where(ep_group > 1,
                            2.0 * n_exp * bytes_p / (pp * ep), 0.0)
    # token dispatch and combine, fwd and bwd, per MoE layer of the stage
    tokens_chip = tokens / (reps * dp * tp)
    a2a_bytes = 4.0 * tokens_chip * topk * dm * bytes_p * (ep - 1.0) / ep \
        * (n_moe / pp)
    # stage-boundary activations (fwd) and their gradients (bwd)
    pp_bytes = xp.where(pp > 1, 2.0 * (gb / (reps * dp)) * seq * dm * 2.0 / tp,
                        0.0)
    pp_dcn = pp * chips_per_pod > CHIPS_PER_POD
    t_ici = (tp_bytes + ag_bytes + gr_bytes + exp_ag_bytes + exp_gr_bytes
             + a2a_bytes + xp.where(pp_dcn, 0.0, pp_bytes)) / ICI_LINK_BW
    # cross-pod gradient all-reduce over DCN (compressed)
    dcn_bytes = xp.where(reps > 1,
                         2.0 * n_total * bytes_p / comp / (pp * chips_per_pod),
                         0.0)
    t_dcn = (dcn_bytes + xp.where(pp_dcn, pp_bytes, 0.0)) / DCN_BW
    t_coll = t_ici + t_dcn

    # --- objectives -----------------------------------------------------------
    bubble = (micro + pp - 1.0) / micro
    latency = xp.maximum(xp.maximum(t_comp, t_mem), t_coll) * bubble
    util = xp.where(latency > 0, t_comp / xp.maximum(latency, 1e-12), 0.0)
    power = chips * (CHIP_IDLE_W + CHIP_DYN_W * util)

    return {"hbm": hbm, "t_comp": t_comp, "ag_bytes": ag_bytes,
            "exp_ag_bytes": exp_ag_bytes, "a2a_bytes": a2a_bytes,
            "bubble": bubble,
            "latency": xp.where(feasible, latency, xp.inf),
            "power": xp.where(feasible, power, xp.inf)}


class TpuMeshModel(DesignModel):
    """Analytic 3-term roofline over (workload, mesh config).

    Both oracles broadcast over arbitrary leading dims — (B,) flat batches
    or (T, C) task-x-candidate grids for the batched Algorithm 2.
    """

    name = "tpu_mesh"

    def __init__(self) -> None:
        self.space = make_mesh_space()
        self.net_space = make_workload_space()

    def evaluate(self, net: np.ndarray, config: np.ndarray):
        net = np.asarray(net, np.float64)
        c = np.asarray(config, np.float64)
        t = roofline_terms(net, c, xp=np)
        return t["latency"], t["power"]

    def evaluate_jax(self, net, config):
        net = jnp.asarray(net, jnp.float32)
        c = jnp.asarray(config, jnp.float32)
        t = roofline_terms(net, c, xp=jnp)
        return t["latency"], t["power"]


#: a qwen3-14b-like dense training job on the generic grid (parse_network
#: snaps DFF 17,408 to 18,432 and 40 heads to 32)
QWEN3_14B_4K = {
    "LAYERS": 40, "DENSE": 0, "MTP": 0, "DMODEL": 5120, "DFF": 17408,
    "EXPERTS": 0, "TOPK": 1, "SHARED": 0, "EFF": 1024, "HEADS": 40,
    "QLORA": 0, "KVLORA": 0, "DNOPE": 128, "DROPE": 0, "DV": 128,
    "VOCAB": 151936, "TIED": 0, "WINDOW": 131072, "SEQ": 4096,
    "GBATCH": 256}

#: DeepSeek-V3 (https://huggingface.co/deepseek-ai/DeepSeek-V3, config.json)
DEEPSEEK_V3 = {
    "LAYERS": 61, "DENSE": 3, "MTP": 1, "DMODEL": 7168, "DFF": 18432,
    "EXPERTS": 256, "TOPK": 8, "SHARED": 1, "EFF": 2048, "HEADS": 128,
    "QLORA": 1536, "KVLORA": 512, "DNOPE": 128, "DROPE": 64, "DV": 128,
    "VOCAB": 129280, "TIED": 0,
    "WINDOW": 131072,       # full causal attention at every phase's length
}
#: the pre-training job's phases (arXiv:2412.19437): 4K pre-training, then
#: 32K and 128K context extension; global batches in sequences
DEEPSEEK_V3_JOBS = {"SEQ": (4096, 32768, 131072),
                    "GBATCH": (480, 1920, 3072, 15360)}


class DeepSeekV3Mesh(TpuMeshModel):
    """Mesh DSE for DeepSeek-V3 training: every architecture dim pinned to
    its published value, the job dims to the report's phases; the mesh
    space is the generic one."""

    name = "tpu_mesh_dsv3"

    def __init__(self) -> None:
        super().__init__()
        grid = {**{k: (v,) for k, v in DEEPSEEK_V3.items()},
                **DEEPSEEK_V3_JOBS}
        self.net_space = ConfigSpace(
            dims=tuple(make_dim(n, grid[n]) for n in NET_DIMS))
