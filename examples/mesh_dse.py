"""Beyond-paper example: GAN-DSE searching a training job's parallelism
design space (replicas x pipeline x dp x tp x ep x microbatch x remat x
dtype x compression) for a target workload, with the TPU roofline as the
design model.

  PYTHONPATH=src python examples/mesh_dse.py
"""
import json

from repro.core.dse_api import GANDSE, parse_network
from repro.core.gan import GANConfig
from repro.design_models.tpu_mesh import QWEN3_14B_4K, TpuMeshModel


def main():
    model = TpuMeshModel()
    cfg = GANConfig(n_net=model.net_space.n_dims, w_critic=0.5).scaled(
        layers=3, neurons=256, batch_size=512, lr=1e-4)
    gandse = GANDSE(model, cfg)
    print("training mesh-DSE explorer...")
    gandse.train(n_data=8000, iters=8, log_every=4)

    # workload: qwen3-14b-like training job (40L x 5120, seq 4096, batch 256)
    net = parse_network(QWEN3_14B_4K, model)

    # objectives: step_time <= 5 s at <= 150 kW cluster power
    result = gandse.explore(net, 5.0, 150e3)
    print(f"satisfied={result.satisfied} "
          f"step_time={result.selection.latency:.3f}s "
          f"power={result.selection.power/1e3:.1f}kW "
          f"dse_time={result.dse_seconds*1e3:.0f}ms")
    if result.satisfied:
        art = gandse.emit_config(result)
        print(json.dumps(art, indent=1))
        c = art["config"]
        chips = int(c["REPLICAS"] * c["PP"] * c["DP"] * c["TP"])
        print(f"-> launch config: {int(c['REPLICAS'])} replica(s) x "
              f"{int(c['PP'])} stage(s) x (data={int(c['DP'])}, "
              f"model={int(c['TP'])}, expert={int(c['EP'])}) = {chips} "
              f"chips, microbatch={int(c['MICRO'])}, remat={bool(c['REMAT'])}, "
              f"param_bytes={int(c['BYTES_P'])}, "
              f"dcn_compression={int(c['COMPRESS'])}x")


if __name__ == "__main__":
    main()
